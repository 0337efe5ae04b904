"""Pallas TPU kernel: chunked linear-attention scan (SSD / scalar decay).

The §Perf iterD "next lever": the pure-JAX recurrence
(``models.linear_attention``) charges HBM for every mini-chunk state
round-trip; this kernel keeps the (dk, dv) state in a VMEM scratch across
the sequential T-grid, so per-chunk traffic is just the q/k/v tiles.

Math (per head; scalar per-token decay a_t = exp(logw_t) <= 1):

    S_t  = a_t S_{t-1} + k_t^T v_t
    o_t  = q_t S_t

Chunked closed form per C-token tile, with L = cumsum(logw) (L_t <= 0,
and L_t - L_i <= 0 for i <= t, so every exponential is <= 1 — stable):

    o      = (q * e^L) @ S_in  +  tril(q k^T * e^{L_t - L_i}) @ v
    S_out  = e^{L_C} S_in + (k * e^{L_C - L})^T @ v

Grid: (B*H, T/C) with T innermost — TPU grids iterate sequentially, so
the VMEM scratch legitimately carries S across T tiles of the same
(batch, head).  The per-channel-decay (RWKV) variant needs the
log-domain ratio trick with clamping and stays on the pure-JAX path.

Validated against ``models.linear_attention.recurrent_scan`` in
interpret mode (tests/test_linear_scan_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from . import backend


def _ssd_kernel(q_ref, k_ref, v_ref, logw_ref, o_ref, state_ref, *,
                n_t_tiles: int) -> None:
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    logw = logw_ref[0].astype(jnp.float32)    # (C,)
    c = q.shape[0]

    el = jnp.cumsum(logw)                     # L_t, <= 0, nonincreasing
    s_in = state_ref[...]
    # inter-chunk: tokens see the carried state decayed to their position
    o_inter = (q * jnp.exp(el)[:, None]) @ s_in
    # intra-chunk: stable because L_t - L_i <= 0 on the kept triangle
    scores = q @ k.T                          # (C, C)
    ratio = jnp.exp(el[:, None] - el[None, :])
    mask = jnp.tril(jnp.ones((c, c), jnp.bool_))
    a = jnp.where(mask, scores * ratio, 0.0)
    o = o_inter + a @ v
    o_ref[0] = o.astype(o_ref.dtype)
    # carry the state to the next T tile
    w_suffix = jnp.exp(el[-1] - el)           # decay token i -> chunk end
    state_ref[...] = jnp.exp(el[-1]) * s_in + (k * w_suffix[:, None]).T @ v


def ssd_scan(q: jax.Array, k: jax.Array, v: jax.Array,
             logw: jax.Array, *, chunk: int = 128) -> jax.Array:
    """q/k: (B, T, H, dk), v: (B, T, H, dv), logw: (B, T, H) (<= 0).

    Returns out (B, T, H, dv) — the scalar-decay linear-attention scan.
    Requires T % chunk == 0 (pad upstream).
    """
    return _ssd_scan(q, k, v, logw, chunk=chunk,
                     interpret=backend.interpret_mode())


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan(q: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
              *, chunk: int, interpret: bool) -> jax.Array:
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if t % chunk:
        raise ValueError(f"T={t} not divisible by chunk={chunk}")
    # (B*H, T, d) layout so the grid is (BH, T/C) with T innermost
    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(b * h, t, a.shape[-1])
    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    wb = logw.transpose(0, 2, 1).reshape(b * h, t)

    grid = (b * h, t // chunk)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, n_t_tiles=t // chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, wb)
    return out.reshape(b, h, t, dv).transpose(0, 2, 1, 3)
