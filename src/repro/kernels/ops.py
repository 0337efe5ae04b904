"""Public jit'd entry points for the kernels package.

``decode_layout`` runs the accelerator-side read module.  The default
(``fused=True``) path executes the compiled
:class:`~repro.core.exec_plan.ExecProgram`: one Pallas kernel gridded
over row tiles decodes the whole buffer against a static slot table —
the TPU analogue of the paper's single HLS ``read_data`` module, one
``pallas_call`` and one jit trace per layout signature.

``fused=False`` keeps the legacy per-(interval, slot) program — one
``pallas_call`` plus one ``dynamic_update_slice`` per decode unit — as
the reference oracle.  In both paths, slots whose element width exceeds
32 bits are decoded by the vectorized numpy host path
(``core.exec_plan`` / ``core.codegen``) instead of raising, so
mixed-width bundles decode end-to-end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codegen import DecodePlan, _gather_bits, decode_plan
from repro.core.exec_plan import ExecProgram
from repro.core.layout import Layout

from .layout_decode import (  # noqa: F401  (HostFallbackWarning re-export)
    HostFallbackWarning,
    decode_layout_fused,
    decode_slot,
    reset_host_fallback_warnings,
)
from .layout_pack import pack_layout_fused  # noqa: F401  (re-export)
from .packed_matmul import packed_matmul  # noqa: F401  (re-export)
from .stream_matmul import (  # noqa: F401  (re-exports)
    stream_matmul,
    stream_words,
)


def buffer_to_u32(buf_u8: np.ndarray | jax.Array) -> jax.Array:
    """(c_max, m/8) uint8 rows -> (c_max, m/32 + 2) uint32 words.

    Two trailing spare words per row so a funnel shift at the last element
    never reads out of bounds (mirrors the packer's spare bytes).
    """
    buf = jnp.asarray(buf_u8, dtype=jnp.uint8)
    c, row_bytes = buf.shape
    # pad each row to a u32 boundary plus two spare words
    pad = (-row_bytes) % 4 + 8
    buf = jnp.pad(buf, ((0, 0), (0, pad)))
    words = buf.reshape(c, (row_bytes + pad) // 4, 4).astype(jnp.uint32)
    shifts = jnp.array([0, 8, 16, 24], dtype=jnp.uint32)
    return jnp.sum(words << shifts[None, None, :], axis=-1, dtype=jnp.uint32)


def decode_layout(layout: Layout, buf_u8: np.ndarray | jax.Array, *,
                  plan: DecodePlan | None = None,
                  fused: bool | None = None,
                  program: ExecProgram | None = None,
                  ) -> dict[str, jax.Array]:
    """Decode an Iris-packed buffer into per-array code streams.

    ``fused=None`` (default) resolves to the fused single-kernel path
    unless a legacy per-slot ``plan`` is supplied — a caller handing in
    a precomputed :class:`DecodePlan` gets the path that consumes it.
    Passing both ``fused=True`` and ``plan`` is a contradiction and
    raises.
    """
    if fused and plan is not None:
        raise ValueError(
            "plan= belongs to the per-slot path; pass program= (or "
            "nothing) for the fused path"
        )
    if fused is None:
        fused = plan is None
    if fused:
        return decode_layout_fused(layout, buf_u8, program=program)
    plan = plan if plan is not None else decode_plan(layout)
    words = buffer_to_u32(buf_u8)
    wide = [s for s in plan.slots if s.width > 32]
    outs = {
        a.name: jnp.zeros(a.depth, dtype=jnp.uint32)
        for a in layout.problem.arrays
        if a.width <= 32
    }
    for slot in plan.slots:
        if slot.width > 32:
            continue                    # host path below
        rows = jax.lax.slice(
            words, (slot.start_cycle, 0),
            (slot.start_cycle + slot.n_cycles, words.shape[1]),
        )
        offsets = tuple(
            slot.bit_offset + k * slot.width for k in range(slot.lanes)
        )
        codes = decode_slot(
            rows,
            offsets=offsets,
            width=slot.width,
            n_rows=slot.n_cycles,
        )
        outs[slot.name] = jax.lax.dynamic_update_slice(
            outs[slot.name], codes, (slot.elem_base,)
        )
    if wide:
        outs.update(_decode_wide_slots_host(layout, buf_u8, wide))
    return outs


def _decode_wide_slots_host(layout: Layout, buf_u8, wide) -> dict:
    """Numpy bit-gather for slots whose width exceeds the u32 kernel path."""
    prob = layout.problem
    row_bytes = prob.m // 8
    buf = np.asarray(buf_u8, dtype=np.uint8)
    padded = np.zeros((layout.c_max, row_bytes + 9), dtype=np.uint8)
    padded[:, :row_bytes] = buf
    outs: dict[str, np.ndarray] = {}
    for slot in wide:
        out = outs.setdefault(
            slot.name,
            np.zeros(prob.arrays[slot.array].depth, dtype=np.uint64))
        rows = padded[slot.start_cycle:slot.start_cycle + slot.n_cycles]
        vals = np.empty((slot.n_cycles, slot.lanes), dtype=np.uint64)
        for k in range(slot.lanes):
            vals[:, k] = _gather_bits(
                rows, slot.bit_offset + k * slot.width, slot.width)
        n = slot.lanes * slot.n_cycles
        out[slot.elem_base:slot.elem_base + n] = vals.reshape(-1)
    # stays numpy uint64: jnp would truncate to 32 bits under the default
    # x64-disabled config
    return outs
