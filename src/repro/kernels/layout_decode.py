"""Pallas TPU kernels: decode an Iris-packed bus buffer into per-array streams.

This is the accelerator-side read module of the paper (Listing 2), adapted
to the TPU memory hierarchy.  Two generations live here:

* :func:`decode_layout_fused` — **one** ``pallas_call`` for the whole
  buffer.  The HLS ``for (t) #pragma HLS pipeline II=1`` loop over bus
  words becomes a single Pallas grid over row tiles; the per-cycle
  ``elem.range(hi, lo)`` arms become a static slot table
  (:class:`~repro.core.exec_plan.KernelTable`): per (row, lane) one
  uint32 encoding ``bit_offset | width << 20``.  Each grid step funnel-
  shifts every lane of its tile out of the packed words (dynamic per-lane
  word gather + shift), writing a row-major ``(rows, lanes)`` uint32
  grid; static per-array gathers then rearrange the grid into element
  streams.  The whole decode jit-traces once per layout signature (the
  trace is memoized on the :class:`~repro.core.exec_plan.ExecProgram`,
  which the layout cache shares across rebinds).  Arrays whose piece
  width exceeds 32 bits are decoded by the vectorized host path and
  merged into the same output dict.
* :func:`decode_slot` — the legacy per-(interval, slot) decode unit, one
  ``pallas_call`` per unit.  Kept as the reference oracle
  (``ops.decode_layout(..., fused=False)``) and for property tests.

Bit conventions match ``core.codegen``: bus rows are little-endian u32
words; an element's LSB sits at ``bit_offset`` and may straddle one word
boundary (never a row boundary) — a two-word funnel shift recovers it.
"""
from __future__ import annotations

import functools
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.exec_plan import _TAB_WIDTH_SHIFT, ExecProgram, lower_exec
from repro.core.layout import Layout
from repro.core.util import round_up as _round_up

from . import backend


class HostFallbackWarning(UserWarning):
    """Fused decode silently routed some arrays to the numpy host path.

    Raised (as a warning) when piece widths exceed ``KERNEL_MAX_WIDTH``:
    those arrays never touch the Pallas kernel, so the decode is not the
    single-launch accelerator pass the caller likely expects.  Carries
    the offending ``(name, width)`` pairs on :attr:`arrays`.  Stream-
    direct matmul avoids this entirely by lowering bundles at element
    granularity (every element width <= 32).

    The message is the only constructor argument, and :attr:`arrays` is
    parsed back out of it, so ``type(w)(*w.args)`` (how pytest-xdist
    ships a warning from a worker) rebuilds an equal warning.  Build one
    from pairs with :meth:`for_arrays`.
    """

    _PAIR = re.compile(r"(\S+) \((\d+)b\)")

    def __init__(self, message: str):
        super().__init__(message)
        detail = message.partition("host path: ")[2]
        self.arrays = tuple((n, int(w)) for n, w in self._PAIR.findall(detail))

    @classmethod
    def for_arrays(cls, arrays) -> "HostFallbackWarning":
        detail = ", ".join(f"{n} ({w}b)" for n, w in arrays)
        return cls(
            f"decode_layout_fused: {len(arrays)} array(s) exceed the "
            f"32-bit kernel piece width and fell back to the host "
            f"path: {detail}. Lower at element granularity "
            "(elem_widths) to keep the decode on-device."
        )

# Rows of the packed buffer processed per grid step.  8 sublanes x 128
# lanes is the native f32/u32 VREG tile; 256 rows keeps the input block
# (256, words) comfortably under VMEM while amortizing control overhead.
DEFAULT_TILE_ROWS = 256

#: (layout signature, array name) pairs already warned about — serving
#: loops decode the same layout thousands of times per second, so the
#: fallback warning fires once per distinct (layout, array), not per call
_FALLBACK_WARNED: set[tuple] = set()


def reset_host_fallback_warnings() -> None:
    """Forget which (layout, array) host fallbacks have been warned about."""
    _FALLBACK_WARNED.clear()


# ----------------------------------------------------------------------
# fused whole-buffer decode (one pallas_call)
# ----------------------------------------------------------------------
def _decode_fused_kernel(words_ref, tab_ref, out_ref) -> None:
    """Decode every lane of a row tile against its static slot table.

    words_ref: (tile, words32) uint32 — packed bus rows.
    tab_ref:   (tile, lanes)   uint32 — ``bit_offset | width << 20``.
    out_ref:   (tile, lanes)   uint32 — decoded piece per (row, lane).
    """
    x = words_ref[...]
    tab = tab_ref[...]
    off = tab & jnp.uint32((1 << _TAB_WIDTH_SHIFT) - 1)
    width = tab >> _TAB_WIDTH_SHIFT
    w0 = (off >> 5).astype(jnp.int32)
    sh = off & jnp.uint32(31)
    last = x.shape[1] - 1
    lo = jnp.take_along_axis(x, w0, axis=1)
    hi = jnp.take_along_axis(x, jnp.minimum(w0 + 1, last), axis=1)
    v = lo >> sh
    # funnel in the straddling word; (32 - sh) & 31 is exact when sh > 0
    hi_part = hi << ((jnp.uint32(32) - sh) & jnp.uint32(31))
    v = v | jnp.where(sh > 0, hi_part, jnp.uint32(0))
    # width == 0 marks an empty lane; width == 32 keeps every bit
    mask = jnp.where(
        width == 0,
        jnp.uint32(0),
        jnp.uint32(0xFFFFFFFF) >> ((jnp.uint32(32) - width) & jnp.uint32(31)),
    )
    out_ref[...] = v & mask


def _fused_grid_fn(prog: ExecProgram, tile_rows: int, interpret: bool):
    """Jitted (words32 -> per-array streams) closure, memoized per program.

    The slot table and gather indices are baked in as constants, so the
    trace happens once per (layout signature, piece widths) — repeated
    decodes, including across LayoutCache rebinds, reuse it.
    """
    key = ("fused", tile_rows, interpret)
    fn = prog.jit_cache.get(key)
    if fn is not None:
        return fn
    kt = prog.kernel
    tile = min(tile_rows, _round_up(prog.c_max, 8))
    padded = _round_up(prog.c_max, tile)
    tab = np.zeros((padded, kt.lanes), dtype=np.uint32)
    tab[:prog.c_max] = kt.tab
    tab_j = jnp.asarray(tab)
    gathers = [(i, jnp.asarray(g)) for i, g in kt.gathers]

    @jax.jit
    def run(words: jax.Array) -> dict[int, jax.Array]:
        if padded != prog.c_max:
            words = jnp.pad(words, ((0, padded - prog.c_max), (0, 0)))
        grid = pl.pallas_call(
            _decode_fused_kernel,
            grid=(padded // tile,),
            in_specs=[
                pl.BlockSpec((tile, kt.words32), lambda i: (i, 0)),
                pl.BlockSpec((tile, kt.lanes), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((tile, kt.lanes), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((padded, kt.lanes), jnp.uint32),
            interpret=interpret,
        )(words, tab_j)
        flat = grid.reshape(-1)
        return {i: jnp.take(flat, g) for i, g in gathers}

    prog.jit_cache[key] = run
    return run


def decode_layout_fused(layout: Layout, buf_u8, *,
                        program: ExecProgram | None = None,
                        elem_widths: tuple[int, ...] | None = None,
                        tile_rows: int = DEFAULT_TILE_ROWS,
                        ) -> dict[str, jax.Array]:
    """Decode the whole packed buffer with a single ``pallas_call``.

    Pieces up to 32 bits wide go through the fused kernel; wider arrays
    are decoded by the vectorized numpy host path
    (:meth:`ExecProgram.unpack_array`) and merged into the result, so
    mixed-width bundles decode end-to-end.
    """
    prog = program if program is not None \
        else lower_exec(layout, elem_widths)
    names = [a.name for a in layout.problem.arrays]
    buf = np.asarray(buf_u8, dtype=np.uint8)
    outs: dict[str, jax.Array] = {}
    if prog.kernel.gathers:
        words = jnp.asarray(prog.buffer_words32(buf))
        kern = _fused_grid_fn(prog, tile_rows,
                              backend.interpret_mode())(words)
        for i, v in kern.items():
            outs[names[i]] = v
    if prog.host_arrays:
        sig = layout.problem.canonical_signature()
        fresh = tuple(
            (names[i], prog.elem_widths[i]) for i in prog.host_arrays
            if (sig, names[i]) not in _FALLBACK_WARNED)
        if fresh:
            _FALLBACK_WARNED.update((sig, n) for n, _w in fresh)
            warnings.warn(HostFallbackWarning.for_arrays(fresh), stacklevel=2)
        flat = prog.buffer_words64(buf)
        for i in prog.host_arrays:
            # stays numpy uint64: jnp would truncate to 32 bits under the
            # default x64-disabled config
            outs[names[i]] = prog.unpack_array(flat, i)
    return outs


# ----------------------------------------------------------------------
# legacy per-(interval, slot) decode unit — the reference oracle
# ----------------------------------------------------------------------
def _decode_slot_kernel(in_ref, out_ref, *, offsets: tuple[int, ...],
                        width: int) -> None:
    """Unpack ``len(offsets)`` fixed-position lanes from each bus row.

    in_ref:  (tile, words) uint32 — packed bus rows.
    out_ref: (tile, lanes) uint32 — one decoded element per lane per row.
    """
    x = in_ref[...]
    mask = jnp.uint32((1 << width) - 1 if width < 32 else 0xFFFFFFFF)
    cols = []
    for off in offsets:
        w0, sh = off // 32, off % 32
        v = x[:, w0]
        if sh:
            v = v >> jnp.uint32(sh)
            if sh + width > 32:
                v = v | (x[:, w0 + 1] << jnp.uint32(32 - sh))
        cols.append(v & mask)
    out_ref[...] = jnp.stack(cols, axis=1)


def decode_slot(rows_u32: jax.Array, *, offsets: tuple[int, ...], width: int,
                n_rows: int, tile_rows: int = DEFAULT_TILE_ROWS) -> jax.Array:
    """Decode one (interval, slot) unit: ``n_rows`` bus rows -> codes.

    ``rows_u32`` is the (n_rows, words) u32 slab of the interval.  Returns
    (n_rows * lanes,) uint32 element codes in stream order.
    """
    return _decode_slot(rows_u32, offsets=offsets, width=width,
                        n_rows=n_rows, tile_rows=tile_rows,
                        interpret=backend.interpret_mode())


@functools.partial(
    jax.jit,
    static_argnames=("offsets", "width", "n_rows", "tile_rows", "interpret"),
)
def _decode_slot(rows_u32: jax.Array, *, offsets: tuple[int, ...],
                 width: int, n_rows: int, tile_rows: int,
                 interpret: bool) -> jax.Array:
    lanes = len(offsets)
    words = rows_u32.shape[1]
    tile = min(tile_rows, _round_up(n_rows, 8))
    padded = _round_up(n_rows, tile)
    if padded != n_rows:
        rows_u32 = jnp.pad(rows_u32, ((0, padded - n_rows), (0, 0)))
    grid = (padded // tile,)
    out = pl.pallas_call(
        functools.partial(_decode_slot_kernel, offsets=offsets, width=width),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, words), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, lanes), jnp.uint32),
        interpret=interpret,
    )(rows_u32)
    return out[:n_rows].reshape(n_rows * lanes)
