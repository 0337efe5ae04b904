"""Pallas TPU kernels: pack per-array streams into an Iris bus buffer.

The inverse of :mod:`repro.kernels.layout_decode`: where the fused decode
funnel-shifts every (row, lane) slot *out* of the packed words, the fused
pack ORs every destination word together *from* its contributing pieces.
The host pack (:meth:`~repro.core.exec_plan.ExecProgram.pack_indexed`)
is a scatter — piece order -> word order — which the XLA CPU backend
executes pathologically (serialized scatter updates).  The device kernel
therefore runs the precomputed gather-only inverse
(:func:`~repro.core.exec_plan.pack_kernel_tables`): per destination u32
word, <= K static (source piece, shift) contributions; the kernel gathers
the flat piece stream through the ``src`` table, shifts by ``scode``
(negative = the hi part of a word-straddling piece, shifted right), and
OR-reduces the K rank layers.  No scatter, no inter-lane dependency —
every grid step is a dense VREG-shaped gather + shift + OR.

The jitted closure is memoized on the
:class:`~repro.core.exec_plan.ExecProgram` (``jit_cache``), so one trace
serves every pack of a layout signature, including across
:class:`~repro.core.iris.LayoutCache` rebinds.  Arrays whose piece width
exceeds ``KERNEL_MAX_WIDTH`` (32) are packed by the vectorized numpy host
path with the kernel arrays zeroed and OR-merged into the same buffer —
bit regions are disjoint by construction, so the merge is exact.

Bit conventions match ``core.codegen``: little-endian u32 bus words; an
element's LSB sits at its bit offset and may straddle one u32 boundary
(never a row boundary).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.exec_plan import (
    ExecProgram,
    lower_exec,
    pack_kernel_tables,
)
from repro.core.layout import Layout
from repro.core.util import round_up as _round_up

from . import backend
from .layout_decode import HostFallbackWarning

# Rows of the packed buffer produced per grid step.  The pack kernel
# reads the *entire* flat piece stream each step (the src table may pull
# any piece into any row tile), so unlike decode the per-step cost has a
# large stream-sized component; big tiles amortize it.  On the interpret
# path each grid step also costs ~0.5ms of fixed overhead — another
# reason to prefer few, large steps.
DEFAULT_TILE_ROWS = 4096

#: (layout signature, array name) pairs already warned about; serving
#: loops pack the same signature repeatedly, so warn once per pair.
_FALLBACK_WARNED: set[tuple] = set()


def reset_host_fallback_warnings() -> None:
    """Forget which (layout, array) host fallbacks have been warned about."""
    _FALLBACK_WARNED.clear()


# ----------------------------------------------------------------------
# fused whole-buffer pack (one pallas_call)
# ----------------------------------------------------------------------
def _pack_fused_kernel(flat_ref, src_ref, sl_ref, sr_ref, neg_ref,
                       out_ref) -> None:
    """OR-assemble a row tile of packed u32 words from the piece stream.

    flat_ref: (n_flat,)          uint32 — piece stream, sentinel 0 at [0].
    src_ref:  (tile, words32*K)  int32  — flat indices (0 = empty slot).
    sl_ref/sr_ref: (tile, words32*K) int32 — left/right shift amounts.
    neg_ref:  (tile, words32*K)  int32  — 1 where the shift is right.
    out_ref:  (tile, words32)    uint32 — packed bus rows.
    """
    flat = flat_ref[...]
    v = jnp.take(flat, src_ref[...])
    c = jnp.where(neg_ref[...] != 0,
                  v >> sr_ref[...].astype(jnp.uint32),
                  v << sl_ref[...].astype(jnp.uint32))
    rows = out_ref.shape[0]
    w32 = out_ref.shape[1]
    k = c.shape[1] // w32
    w = c.reshape(rows, w32, k)
    acc = w[:, :, 0]
    for j in range(1, k):
        acc = acc | w[:, :, j]
    out_ref[...] = acc


def _fused_pack_fn(prog: ExecProgram, tile_rows: int, interpret: bool):
    """Jitted (flat piece stream -> words32 buffer) closure, memoized
    per program.

    Tables are baked in as constants: the trace happens once per (layout
    signature, piece widths, tile, interpret) and is shared across
    LayoutCache rebinds via the program's ``jit_cache``.
    """
    key = ("pack", tile_rows, interpret)
    fn = prog.jit_cache.get(key)
    if fn is not None:
        return fn
    src_t, sc_t, k = pack_kernel_tables(prog)
    w32 = prog.kernel.words32
    tile = min(tile_rows, _round_up(prog.c_max, 8))
    padded = _round_up(prog.c_max, tile)

    def _pad(a: np.ndarray) -> jax.Array:
        out = np.zeros((padded, a.shape[1]), dtype=a.dtype)
        out[:prog.c_max] = a
        return jnp.asarray(out)

    src_j = _pad(src_t)
    sl_j = _pad(np.clip(sc_t, 0, 31).astype(np.int32))
    sr_j = _pad(np.clip(-sc_t, 0, 31).astype(np.int32))
    neg_j = _pad((sc_t < 0).astype(np.int32))
    n_flat = prog.n_pieces + 1
    cols = w32 * k

    @jax.jit
    def run(flat: jax.Array) -> jax.Array:
        out = pl.pallas_call(
            _pack_fused_kernel,
            grid=(padded // tile,),
            in_specs=[
                pl.BlockSpec((n_flat,), lambda i: (0,)),
                pl.BlockSpec((tile, cols), lambda i: (i, 0)),
                pl.BlockSpec((tile, cols), lambda i: (i, 0)),
                pl.BlockSpec((tile, cols), lambda i: (i, 0)),
                pl.BlockSpec((tile, cols), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((tile, w32), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((padded, w32), jnp.uint32),
            interpret=interpret,
        )(flat, src_j, sl_j, sr_j, neg_j)
        return out[:prog.c_max]

    prog.jit_cache[key] = run
    return run


def _check_stream(name: str, a, depth: int, ew: int) -> np.ndarray:
    arr = np.asarray(a).reshape(-1)
    if arr.dtype != np.uint64:
        arr = arr.astype(np.uint64)
    if arr.shape[0] != depth:
        raise ValueError(
            f"{name}: expected {depth} elements, got {arr.shape[0]}")
    if ew < 64 and (arr >> np.uint64(ew)).any():
        raise ValueError(f"{name}: codes overflow {ew} bits")
    return arr


def pack_layout_fused(layout: Layout, arrays: dict, *,
                      program: ExecProgram | None = None,
                      elem_widths: tuple[int, ...] | None = None,
                      tile_rows: int = DEFAULT_TILE_ROWS) -> np.ndarray:
    """Pack per-array piece streams with a single ``pallas_call``.

    Bit-identical to :func:`~repro.core.exec_plan.pack_compiled`: returns
    the same ``(c_max, m/8)`` uint8 buffer.  Pieces up to 32 bits wide go
    through the fused kernel; wider arrays are packed by the numpy host
    path (kernel arrays zeroed) and OR-merged — their bit regions are
    disjoint, so the merge is exact.
    """
    prog = program if program is not None \
        else lower_exec(layout, elem_widths)
    specs = layout.problem.arrays
    names = [a.name for a in specs]
    for name in names:
        if name not in arrays:
            raise KeyError(f"missing array {name!r}")
    streams = [
        _check_stream(names[i], arrays[names[i]],
                      prog.piece_depths[i], prog.elem_widths[i])
        for i in range(len(specs))]

    out32: np.ndarray | None = None
    if prog.kernel.gathers:
        flat = np.zeros(prog.n_pieces + 1, dtype=np.uint32)
        for i, _g in prog.kernel.gathers:
            flat[1 + prog.piece_base[i]:1 + prog.piece_base[i + 1]] = \
                streams[i].astype(np.uint32)
        run = _fused_pack_fn(prog, tile_rows, backend.interpret_mode())
        out32 = np.asarray(jax.block_until_ready(run(jnp.asarray(flat))))

    if prog.host_arrays:
        sig = layout.problem.canonical_signature()
        fresh = tuple(
            (names[i], prog.elem_widths[i]) for i in prog.host_arrays
            if (sig, names[i]) not in _FALLBACK_WARNED)
        if fresh:
            _FALLBACK_WARNED.update((sig, n) for n, _w in fresh)
            warnings.warn(HostFallbackWarning.for_arrays(fresh), stacklevel=2)
        host_set = set(prog.host_arrays)
        host_data = [
            s if i in host_set else np.zeros_like(s)
            for i, s in enumerate(streams)]
        host_buf = prog.pack_indexed(host_data)
        host32 = prog.buffer_words32(host_buf)
        out32 = host32 if out32 is None else out32 | host32

    if out32 is None:               # degenerate: a problem with no arrays
        out32 = np.zeros((prog.c_max, prog.kernel.words32), dtype=np.uint32)
    return np.ascontiguousarray(out32).view(np.uint8).reshape(
        prog.c_max, prog.kernel.words32 * 4)[:, :prog.row_bytes]
