"""Stream-direct decode attention: Iris KV pages -> registers -> dot.

The K/V prologue reads the packed pages the way
``repro.kernels.stream_matmul`` reads weights: each program instance
(one batch slot) funnel-shifts its codes and bf16 scale bit patterns
straight out of the slot's page words, dequantizes in registers, and
feeds the decode attention math — no dense K/V tensor ever exists in
HBM.

Every page packs the same layout, so one set of page-local window
entries (:func:`repro.kvcache.layout.page_window_tables`) serves all of
them.  A slot's pages are staged as ``(n_chunks, n_pages, 128)`` word
rows; the kernel spreads chunk ``c`` of each page over that page's token
rows and lane-gathers every field within its row
(:func:`repro.kernels.window.window_extract`), the gather form Mosaic
lowers.  K and V come out as ``(n_kv_heads, smax, head_dim)``.

The attention body reproduces
:func:`repro.models.attention.decode_attention` op for op (GQA
replication, the same batched contractions with
``preferred_element_type=f32``, position mask at ``NEG_INF``, f32
softmax and V contraction) so the kernel's output is bit-identical to
running the dense path on the materialized dequantized K/V — the gate
``tests/test_kvcache.py`` asserts.

Every grid step holds the whole sequence (``smax`` tokens) of one slot;
VMEM bounds ``smax`` until the sequence is tiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro.kernels.window import LANES, window_extract
from repro.kvcache.layout import page_window_tables
from repro.models.attention import NEG_INF


def stage_pages(words: jax.Array, n_pages: int) -> jax.Array:
    """``(B, n_pages * page_words)`` slot words -> ``(B, n_chunks,
    n_pages, 128)``: each page cut into 128-word chunk rows."""
    b = words.shape[0]
    pw = words.shape[1] // n_pages
    n_ch = -(-pw // LANES)
    w = words.reshape(b, n_pages, pw)
    if n_ch * LANES != pw:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, n_ch * LANES - pw)))
    return w.reshape(b, n_pages, n_ch, LANES).transpose(0, 2, 1, 3)


def _attention_kernel(pos_ref, words_ref, q_ref, k_ref, ks_ref, v_ref,
                      vs_ref, o_ref, *, bits: int, head_dim: int) -> None:
    _, n_ch, n_pages, _ = words_ref.shape
    hkv, pt, _ = k_ref.shape
    smax = n_pages * pt
    rows = hkv * smax
    bias = float(2 ** (bits - 1))

    def spread(a):       # (n_pages, 128) -> a page's words on its rows
        return jnp.broadcast_to(a[None, :, None, :],
                                (hkv, n_pages, pt, LANES)).reshape(
                                    rows, LANES)

    def tile(t):         # page-local entries -> every page
        return jnp.broadcast_to(t[:, None], (hkv, n_pages, pt, LANES)) \
            .reshape(rows, LANES)

    srcs = [spread(words_ref[0, c]) for c in range(n_ch)]

    def dequant(ent_ref, sent_ref):
        codes = window_extract(srcs, tile(ent_ref[...]), bits)
        sc16 = window_extract(srcs, tile(sent_ref[...]), 16)
        scale = jax.lax.bitcast_convert_type(sc16 << 16, jnp.float32)
        # via int32: Mosaic has no uint32 -> float32 conversion
        x = (codes.astype(jnp.int32).astype(jnp.float32) - bias) * scale
        return x.reshape(hkv, smax, LANES)[:, :, :head_dim]

    kf = dequant(k_ref, ks_ref)                      # (hkv, smax, hd)
    vf = dequant(v_ref, vs_ref)
    q = q_ref[0]                                     # (H, 1, hd)
    h = q.shape[0]
    if hkv != h:                                     # GQA replication
        kf = jnp.repeat(kf, h // hkv, axis=0)
        vf = jnp.repeat(vf, h // hkv, axis=0)
    kc = kf.astype(q.dtype)
    vc = vf.astype(q.dtype)
    s = jnp.einsum("hqd,hkd->hqk", q, kc,
                   preferred_element_type=jnp.float32) * head_dim ** -0.5
    iota = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(iota <= pos_ref[pl.program_id(0)], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hqk,hkd->hqd", p, vc.astype(jnp.float32))
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def stream_attention_call(words: jax.Array, q: jax.Array, pos: jax.Array,
                          k_ent: jax.Array, ks_ent: jax.Array,
                          v_ent: jax.Array, vs_ent: jax.Array, *,
                          bits: int, interpret: bool) -> jax.Array:
    """The jitted kernel launch behind :func:`stream_attention`."""
    b, n_ch, n_pages, _ = words.shape
    _, h, _, hd = q.shape
    ent = pl.BlockSpec(k_ent.shape, lambda i, *_: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, n_ch, n_pages, LANES),
                         lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, 1, hd), lambda i, *_: (i, 0, 0, 0)),
            ent, ent, ent, ent,
        ],
        out_specs=pl.BlockSpec((1, h, 1, hd), lambda i, *_: (i, 0, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_attention_kernel, bits=bits, head_dim=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), words, q, k_ent, ks_ent, v_ent, vs_ent)


def stream_attention(words: jax.Array, q: jax.Array, pos: jax.Array,
                     tables: dict, *, bits: int) -> jax.Array:
    """Decode attention over packed KV pages, one program per slot.

    ``words``: ``(B, n_chunks, n_pages, 128)`` uint32 staged pages
    (:func:`stage_pages`); ``q``: ``(B, 1, H, hd)``; ``pos``: ``(B,)``
    per-slot positions; ``tables``: the page-local window entries of
    :func:`repro.kvcache.layout.page_window_tables`.  Returns
    ``(B, 1, H, hd)`` in ``q.dtype``.
    """
    b, _, h, hd = q.shape
    hkv = tables["k"].shape[0]
    if h % hkv:
        raise ValueError(f"{h} query heads do not group over {hkv} KV heads")
    as_dev = {k: jnp.asarray(t) for k, t in tables.items()}
    # heads lead and the query sits in a unit sublane dim, so the kernel
    # contracts per head without reshaping (Mosaic refuses that cast)
    out = stream_attention_call(
        words, q.reshape(b, h, 1, hd), pos, as_dev["k"],
        as_dev["k_scales"], as_dev["v"], as_dev["v_scales"], bits=bits,
        interpret=backend.interpret_mode())
    return out.reshape(b, 1, h, hd)


def stream_attention_cache(kvc, q: jax.Array, pos: jax.Array,
                           slot_ids: jax.Array, *, layer: int) -> jax.Array:
    """Convenience front door: stage a :class:`PackedKVCache` layer's
    active slots and run :func:`stream_attention` against its tables."""
    man = kvc.manifest
    prog = kvc.program()
    # device forms memoized beside the numpy tables (program jit_cache)
    key = ("kv_window_device", man.page_tokens, man.n_kv_heads,
           man.head_dim, man.bits)
    tabs = prog.jit_cache.get(key)
    if tabs is None:
        tabs = {k: jnp.asarray(t) for k, t in page_window_tables(
            prog, page_tokens=man.page_tokens, n_kv_heads=man.n_kv_heads,
            head_dim=man.head_dim, bits=man.bits).items()}
        prog.jit_cache[key] = tabs
    words = stage_pages(kvc.slot_words(layer, slot_ids), man.n_pages)
    return stream_attention(words, q, pos, tabs, bits=man.bits)


__all__ = ["stage_pages", "stream_attention", "stream_attention_call",
           "stream_attention_cache"]
