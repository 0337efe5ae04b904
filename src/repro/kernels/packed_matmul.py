"""Pallas TPU kernel: dequant-on-load matmul over lane-packed intN weights.

The compute hot-spot of Iris-packed serving: activations hit quantized
weights that are *streamed packed* from HBM (bits moved = N*K*bits/8, not
N*K padded bytes) and dequantized in VMEM right before the MXU.

TPU adaptation of the paper's decode->stream->kernel dataflow (Listing 2
feeding the downstream dataflow modules): instead of per-cycle bit-slices
feeding FIFOs, each grid step DMAs a (bk*bits/32, bn) packed block into
VMEM, funnel-shifts it into a (bk, bn) int grid, applies group scales, and
feeds the MXU — the dequant is fused into the matmul pipeline so the
packed->dense expansion never touches HBM.

Blocking: grid (M/bm, N/bn, K/bk), K innermost; a VMEM f32 accumulator
carries partial sums across K steps.  bm/bn/bk default to MXU-aligned 128
multiples; bk must be a multiple of the quantization group size.  M may
be ragged (serving batch sizes are): activations are zero-padded up to
the M tile internally and the padding sliced off the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from . import backend

#: element widths the lane-packed kernel path supports: the funnel shift
#: needs a whole number of lanes per uint32 word (32 % bits == 0).  The
#: serving CLI (`launch.serve --bits`) and `api.pack_tree` validate
#: against this set up front instead of erroring inside the kernel.
SUPPORTED_BITS = (2, 4, 8)


def _packed_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                          bits: int, group_size: int, n_k_steps: int) -> None:
    lanes = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)
    bias = float(1 << (bits - 1))

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_packed = w_ref[...]                      # (bk // lanes, bn) uint32
    rows, bn = w_packed.shape
    bk = rows * lanes
    # funnel-shift each lane out of its word: lane ln of word r is code
    # k = r * lanes + ln  ->  (rows, lanes, bn) -> (bk, bn)
    planes = [
        ((w_packed >> jnp.uint32(ln * bits)) & mask) for ln in range(lanes)
    ]
    codes = jnp.stack(planes, axis=1).reshape(bk, bn)
    # via int32: Mosaic has no uint32 -> float32 conversion
    wq = codes.astype(jnp.int32).astype(jnp.float32) - bias
    scales = s_ref[...].astype(jnp.float32)    # (bk // group_size, bn)
    wf = (wq.reshape(bk // group_size, group_size, bn)
          * scales[:, None, :]).reshape(bk, bn)
    x = x_ref[...].astype(jnp.float32)         # (bm, bk)
    acc_ref[...] += jnp.dot(x, wf, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def packed_matmul(x: jax.Array, w_packed: jax.Array, scales: jax.Array, *,
                  bits: int, group_size: int, block_m: int = 128,
                  block_n: int = 128, block_k: int = 512,
                  out_dtype=jnp.float32) -> jax.Array:
    """``x @ dequant(w_packed, scales)`` with on-the-fly dequantization.

    x:        (M, K) float
    w_packed: (K * bits // 32, N) uint32 lane-packed codes
              (see ``quant.pack_codes_u32``)
    scales:   (K // group_size, N)
    """
    return packed_matmul_call(
        x, w_packed, scales, bits=bits, group_size=group_size,
        block_m=block_m, block_n=block_n, block_k=block_k,
        out_dtype=out_dtype, interpret=backend.interpret_mode())


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits", "group_size", "block_m", "block_n", "block_k", "interpret",
        "out_dtype",
    ),
)
def packed_matmul_call(x: jax.Array, w_packed: jax.Array, scales: jax.Array,
                       *, bits: int, group_size: int, block_m: int,
                       block_n: int, block_k: int, out_dtype,
                       interpret: bool) -> jax.Array:
    """The jitted kernel launch behind :func:`packed_matmul`."""
    if bits not in SUPPORTED_BITS:
        raise ValueError(
            f"packed_matmul supports bits in {sorted(SUPPORTED_BITS)}; "
            f"got {bits}"
        )
    m, k = x.shape
    lanes = 32 // bits
    kw, n = w_packed.shape
    if kw * lanes != k:
        raise ValueError(f"packed K mismatch: {kw}*{lanes} != {k}")
    if scales.shape != (k // group_size, n):
        raise ValueError(f"scales shape {scales.shape} != {(k // group_size, n)}")
    block_m = min(block_m, -(-m // 8) * 8)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if k % block_k or block_k % group_size:
        raise ValueError(
            f"K={k} must tile by block_k={block_k}, "
            f"block_k by group_size={group_size}"
        )
    if n % block_n:
        raise ValueError(f"N={n} must tile by block_n={block_n}")
    # serving batches are ragged: pad activations up to the M tile and
    # slice the padding back off the output (zero rows cost one tile at
    # most and never perturb real rows)
    m_pad = -(-m // block_m) * block_m
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    n_k_steps = k // block_k
    grid = (m_pad // block_m, n // block_n, n_k_steps)

    kernel = functools.partial(
        _packed_matmul_kernel,
        bits=bits,
        group_size=group_size,
        n_k_steps=n_k_steps,
    )
    # pltpu.VMEM scratch works in interpret mode too (plain f32 buffer)
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k * bits // 32, block_n),
                         lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // group_size, block_n),
                         lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="packed_matmul_call",
    )(x, w_packed, scales)
    return out[:m] if m_pad != m else out
