"""Pallas TPU kernel: matmul straight out of an Iris-packed stream.

The legacy serving path is two passes — ``decode_layout_fused``
materializes dense codes/scales in HBM, then ``packed_matmul`` re-reads
them — paying the packed->dense expansion in memory traffic twice, which
is exactly the redundant transfer the paper's scheduled layout exists to
eliminate.  This kernel makes the decode part of the matmul *prologue*:
each grid tile gathers the packed words it needs from the stream buffer,
funnel-shifts codes and bf16 scale patterns out in registers,
dequantizes, and feeds the MXU.  HBM -> VMEM -> registers -> MXU, no
dense intermediate.

The extraction is table-driven and *windowed* (:mod:`repro.kernels.window`):
the stream is staged as ``(R, 128)`` uint32 rows, and each 128-lane chunk
of an operand-tile row lists the few stream rows its fields live in
(scalar-prefetched into SMEM).  The kernel loads those rows at dynamic
sublane offsets and lane-gathers every field within its row — the only
data-dependent gather Mosaic lowers.  Because the tables address bits,
not lanes, any piece width <= 32 works — this is what lifts
``packed_matmul``'s ``SUPPORTED_BITS=(2, 4, 8)`` restriction (int3 LM
bundles become servable end-to-end).

Blocking mirrors ``packed_matmul`` — grid (M/bm, N/bn, K/bk) with K
innermost and a VMEM f32 accumulator — so on shapes both kernels accept
the two paths perform the identical float ops in the identical order and
agree *bit-for-bit* (locked down by tests/test_stream_matmul.py).  The
operand tiles are extracted in whole 128-lane chunks and cut back to the
N block before the dot; a ragged K is handled by padding the tables and
masking the dequantized tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.exec_plan import StreamTables
from repro.core.util import round_up as _round_up

from . import backend
from .window import LANES, row_window_tables, window_extract

#: sublanes of one VREG tile: the operand tiles are filled 8 rows at a time
_SUBLANES = 8


def window_operands(tables: StreamTables, block_n: int) -> dict:
    """Device-resident window tables of ``tables`` for ``block_n``-column
    tiles (built once per tile width, memoized in the program's
    ``jit_cache`` and so shared across layers and rebinds).

    ``w_ent`` ``(K, Nf)`` / ``s_ent`` ``(K/g, Nf)`` uint32 entries and
    ``w_rows`` ``(K, Nf/128, Uw)`` / ``s_rows`` ``(K/g, Nf/128, Us)``
    int32 stream-row lists, ``Nf`` = N tiles x ``round_up(block_n, 128)``
    (:func:`repro.kernels.window.row_window_tables`).
    """
    cache = tables.program.jit_cache
    key = ("stream", tables.key, tables.group_size, block_n)
    ops = cache.get(key)
    if ops is None:
        w_ent, w_rows = row_window_tables(tables.w_tab, tables.bits, block_n)
        s_ent, s_rows = row_window_tables(tables.s_tab, 16, block_n)
        ops = {"w_ent": jnp.asarray(w_ent), "w_rows": jnp.asarray(w_rows),
               "s_ent": jnp.asarray(s_ent), "s_rows": jnp.asarray(s_rows)}
        cache[key] = ops
    return ops


def _fill_tile(words_ref, ent_ref, rows_ref, out_ref, *, row0, chunk0,
               n_chunks: int, n_rows: int, n_u: int, width: int) -> None:
    """Extract an operand tile into ``out_ref``, 8 rows x 128 lanes at a
    time: per output row, load its ``n_u`` listed stream rows and
    lane-gather every field out of them."""
    for c in range(out_ref.shape[1] // LANES):
        chunk = chunk0 + c

        def body(gi, carry, c=c, chunk=chunk):
            r0 = pl.multiple_of(gi * _SUBLANES, _SUBLANES)
            ent = ent_ref[pl.ds(r0, _SUBLANES), pl.ds(c * LANES, LANES)]
            srcs = []
            for u in range(n_u):
                parts = [
                    words_ref[pl.ds(rows_ref[
                        ((row0 + r0 + r) * n_chunks + chunk) * n_u + u],
                        1), :]
                    for r in range(_SUBLANES)]
                srcs.append(jnp.concatenate(parts, axis=0))
            out_ref[pl.ds(r0, _SUBLANES), pl.ds(c * LANES, LANES)] = \
                window_extract(srcs, ent, width)
            return carry

        jax.lax.fori_loop(0, n_rows // _SUBLANES, body, 0)


def _stream_matmul_kernel(w_rows_ref, s_rows_ref, x_ref, words_ref,
                          w_ent_ref, s_ent_ref, o_ref, acc_ref, codes_ref,
                          spat_ref, *, bits: int, group_size: int,
                          n_k_steps: int, n_chunks: int, bk: int,
                          n_uw: int, n_us: int, k_true: int | None) -> None:
    bias = float(1 << (bits - 1))
    j, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk8, fill = codes_ref.shape
    bn = o_ref.shape[1]
    sk8 = spat_ref.shape[0]
    chunk0 = j * (fill // LANES)
    _fill_tile(words_ref, w_ent_ref, w_rows_ref, codes_ref, row0=kk * bk8,
               chunk0=chunk0, n_chunks=n_chunks, n_rows=bk8, n_u=n_uw,
               width=bits)
    _fill_tile(words_ref, s_ent_ref, s_rows_ref, spat_ref, row0=kk * sk8,
               chunk0=chunk0, n_chunks=n_chunks, n_rows=sk8, n_u=n_us,
               width=16)
    sk = bk // group_size
    codes = codes_ref[...] if (bk8, fill) == (bk, bn) \
        else codes_ref[:bk, :bn]
    spat = spat_ref[...] if (sk8, fill) == (sk, bn) \
        else spat_ref[:sk, :bn]
    # via int32: Mosaic has no uint32 -> float32 conversion
    wq = codes.astype(jnp.int32).astype(jnp.float32) - bias
    scales = jax.lax.bitcast_convert_type(
        spat << jnp.uint32(16), jnp.float32)   # == bf16.astype(f32)
    wf = (wq.reshape(sk, group_size, bn)
          * scales[:, None, :]).reshape(bk, bn)
    # ragged K: padded table rows decode garbage (possibly NaN scale
    # patterns) — zero them so 0 * NaN never reaches the accumulator.
    # Padded N columns only ever reach their own (sliced-off) outputs.
    # Static None means no padding and keeps the unpadded path
    # bit-identical to packed_matmul.
    if k_true is not None:
        krow = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)
        wf = jnp.where(krow < k_true, wf, 0.0)
    x = x_ref[...].astype(jnp.float32)         # (bm, bk)
    acc_ref[...] += jnp.dot(x, wf, preferred_element_type=jnp.float32)

    @pl.when(kk == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tile_rows(ent, rows, *, rows_pad: int, n_steps: int, step: int,
               step8: int):
    """Pad tables to whole K steps of ``step8`` rows (a VREG multiple);
    flatten the row lists for SMEM."""
    r, cols = ent.shape
    chunks = rows.shape[1]
    ent = jnp.pad(ent, ((0, rows_pad - r), (0, 0)))
    rows = jnp.pad(rows, ((0, rows_pad - r), (0, 0), (0, 0)))
    if step8 != step:
        ent = jnp.pad(ent.reshape(n_steps, step, cols),
                      ((0, 0), (0, step8 - step), (0, 0)))
        rows = jnp.pad(rows.reshape(n_steps, step, chunks, -1),
                       ((0, 0), (0, step8 - step), (0, 0), (0, 0)))
    return ent.reshape(n_steps * step8, cols), rows.reshape(-1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits", "group_size", "n", "block_m", "block_n", "block_k",
        "out_dtype", "interpret",
    ),
)
def stream_matmul_call(x: jax.Array, stream_words: jax.Array,
                       w_ent: jax.Array, w_rows: jax.Array,
                       s_ent: jax.Array, s_rows: jax.Array, *, bits: int,
                       group_size: int, n: int, block_m: int, block_n: int,
                       block_k: int, out_dtype, interpret: bool
                       ) -> jax.Array:
    """The jitted kernel launch behind :func:`stream_matmul`: operands
    from :func:`window_operands` for ``block_n``-column tiles of the
    ``n``-column weight; ``interpret`` from the backend."""
    m, k = x.shape
    g = group_size
    n_tiles = -(-n // block_n)
    fill = w_ent.shape[1] // n_tiles
    block_m = min(block_m, _round_up(m, _SUBLANES))
    block_k = _round_up(min(block_k, k), g)
    m_pad = _round_up(m, block_m)
    n_pad = n_tiles * block_n
    k_pad = _round_up(k, block_k)
    if m_pad != m or k_pad != k:
        x = jnp.pad(x, ((0, m_pad - m), (0, k_pad - k)))
    n_k_steps = k_pad // block_k
    n_chunks = w_rows.shape[1]
    bk8 = _round_up(block_k, _SUBLANES)
    sk = block_k // g
    sk8 = _round_up(sk, _SUBLANES)
    w_ent, w_rows_flat = _tile_rows(
        w_ent, w_rows, rows_pad=k_pad, n_steps=n_k_steps, step=block_k,
        step8=bk8)
    s_ent, s_rows_flat = _tile_rows(
        s_ent, s_rows, rows_pad=k_pad // g, n_steps=n_k_steps, step=sk,
        step8=sk8)

    # stage the stream as VREG-aligned (R, 128) rows; every grid step
    # sees the whole buffer (the row lists are data-dependent)
    flat = stream_words.reshape(-1)
    s_len = _round_up(flat.shape[0], LANES * _SUBLANES)
    if s_len != flat.shape[0]:
        flat = jnp.pad(flat, (0, s_len - flat.shape[0]))
    words2d = flat.reshape(s_len // LANES, LANES)

    kernel = functools.partial(
        _stream_matmul_kernel,
        bits=bits,
        group_size=g,
        n_k_steps=n_k_steps,
        n_chunks=n_chunks,
        bk=block_k,
        n_uw=w_rows.shape[-1],
        n_us=s_rows.shape[-1],
        k_true=k if k_pad != k else None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m_pad // block_m, n_tiles, n_k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk, *_: (i, kk)),
            pl.BlockSpec(words2d.shape, lambda i, j, kk, *_: (0, 0)),
            pl.BlockSpec((bk8, fill), lambda i, j, kk, *_: (kk, j)),
            pl.BlockSpec((sk8, fill), lambda i, j, kk, *_: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, kk, *_: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((bk8, fill), jnp.uint32),
            pltpu.VMEM((sk8, fill), jnp.uint32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype),
        interpret=interpret,
        name="stream_matmul_call",
    )(w_rows_flat, s_rows_flat, x, words2d, w_ent, s_ent)
    return out[:m, :n] if (m_pad, n_pad) != (m, n) else out


def stream_matmul(x: jax.Array, stream_words: jax.Array,
                  tables: StreamTables, *, block_m: int = 128, block_n: int = 128,
                  block_k: int = 512, out_dtype=jnp.float32) -> jax.Array:
    """``x @ dequant(stream)`` gathering weights straight from the stream.

    x:            (M, K) float activations
    stream_words: uint32 packed stream, the flattened
                  :meth:`~repro.core.exec_plan.ExecProgram.buffer_words32`
                  view (any shape; flattened row-major)
    tables:       the matrix's :class:`~repro.core.exec_plan.StreamTables`
                  (``w_tab`` (K, N) / ``s_tab`` (K/g, N) bit offsets)

    Any ``1 <= bits <= 32`` is supported; M, K and N may all be ragged.
    M is padded to whole 8-row tiles; an N that ``block_n`` does not
    divide is one whole-width block.
    """
    bits, g = tables.bits, tables.group_size
    if not 1 <= bits <= 32:
        raise ValueError(f"bits must be in [1, 32]; got {bits}")
    k = x.shape[1]
    kt, n = tables.w_tab.shape
    if kt != k:
        raise ValueError(f"w_tab K {kt} != activations K {k}")
    if k % g:
        raise ValueError(f"K={k} not divisible by group_size={g}")
    if tables.s_tab.shape != (k // g, n):
        raise ValueError(
            f"s_tab shape {tables.s_tab.shape} != {(k // g, n)}")
    if stream_words.dtype != jnp.uint32:
        raise ValueError(f"stream must be uint32, got {stream_words.dtype}")
    if tables.w_tab.dtype != np.uint32 or tables.s_tab.dtype != np.uint32:
        raise ValueError("offset tables must be uint32")
    # a ragged N is one whole-width block, as in packed_matmul's tiling
    block_n = block_n if n % block_n == 0 else n
    ops = window_operands(tables, block_n)
    return stream_matmul_call(
        x, stream_words, ops["w_ent"], ops["w_rows"], ops["s_ent"],
        ops["s_rows"], bits=bits, group_size=g, n=n, block_m=block_m,
        block_n=block_n, block_k=block_k, out_dtype=out_dtype,
        interpret=backend.interpret_mode())


def stream_words(program, buf_u8) -> jax.Array:
    """Packed ``(c_max, m/8)`` buffer -> flat uint32 device stream.

    One host-side conversion at load time; every subsequent
    :func:`stream_matmul` reads the same device array.
    """
    return jnp.asarray(program.buffer_words32(buf_u8).reshape(-1))
