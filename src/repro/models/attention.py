"""GQA attention: double-chunked flash for train/prefill, direct for decode.

Train/prefill uses an online-softmax formulation chunked over BOTH query
and key/value blocks (``lax.map`` over q blocks, ``lax.scan`` over kv
blocks) so peak memory is O(q_chunk * kv_chunk) per head instead of
O(S^2) — the TPU-native equivalent of flash attention, expressed in pure
lax so GSPMD can shard it.

Decode (one query token) uses the direct einsum path: logits are
(B, 1, H, S) which is small at any context length and — crucially for
long_500k — contracts cleanly against a sequence-sharded KV cache (XLA
inserts the partial-softmax psum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import apply_rope, dense_init

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, hkv * hd, dtype),
        "wv": dense_init(ks[2], d, hkv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype, scale=(h * hd) ** -0.5),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def _project(cfg, p, x, name):
    y = x @ p[f"w{name}"]
    if cfg.use_bias:
        y = y + p[f"b{name}"]
    return y


def _repeat_kv(kv: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, Hkv, hd) -> (B, S, H, hd) by GQA group replication."""
    hkv = kv.shape[2]
    if hkv == n_heads:
        return kv
    return jnp.repeat(kv, n_heads // hkv, axis=2)


# ----------------------------------------------------------------------
# chunked flash attention (train / prefill)
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "q_chunk", "kv_chunk"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_chunk: int = 1024,
                    kv_chunk: int = 1024) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Skv, Hkv, hd).  Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = hd ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nkv = -(-sq // q_chunk), -(-skv // kv_chunk)
    pad_q, pad_kv = nq * q_chunk - sq, nkv * kv_chunk - skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    qb = q.reshape(b, nq, q_chunk, h, hd)
    kb = k.reshape(b, nkv, kv_chunk, h, hd)
    vb = v.reshape(b, nkv, kv_chunk, h, hd)

    def q_block(args):
        qi, q_base = args                       # (B, cq, H, hd), scalar

        def kv_step(carry, inputs):
            m, lsum, acc = carry
            kj, vj, kv_base = inputs
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            q_pos = q_base + jnp.arange(q_chunk)
            kv_pos = kv_base + jnp.arange(kv_chunk)
            mask = kv_pos[None, :] < skv                       # kv padding
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = lsum * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vj.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        kv_bases = jnp.arange(nkv) * kv_chunk
        # remat the body: backward recomputes the (cq, ckv) score tile
        # instead of saving one per scan step (which would materialize the
        # full S^2 matrix as scan residuals — the whole point of flash
        # attention is not to do that)
        (m, lsum, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             kv_bases),
        )
        out = acc / jnp.maximum(lsum, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3)                  # (B, cq, H, hd)

    q_bases = jnp.arange(nq) * q_chunk
    outs = jax.lax.map(q_block, (qb.transpose(1, 0, 2, 3, 4), q_bases))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_chunk, h, hd)
    return out[:, :sq].astype(q.dtype)


# ----------------------------------------------------------------------
# decode attention (single query position, KV cache)
# ----------------------------------------------------------------------
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array) -> jax.Array:
    """q: (B, 1, H, hd); caches: (B, Smax, Hkv, hd); pos: (B,) per-row
    positions (continuous batching: every slot has its own clock).

    Direct einsum: logits (B, H, 1, Smax) are tiny for Sq=1 and contract
    against a sequence-sharded cache without re-chunking.
    """
    b, _, h, hd = q.shape
    smax = k_cache.shape[1]
    # low-precision caches (fp8 KV) are upcast at the compute boundary
    kc = _repeat_kv(k_cache, h).astype(q.dtype)
    vc = _repeat_kv(v_cache, h).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    valid = jnp.arange(smax)[None, None, None, :] <= \
        pos[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32))
    return out.astype(q.dtype)


def stream_decode_attention(kvc, q: jax.Array, pos: jax.Array,
                            slot_ids: jax.Array, *, layer: int,
                            oracle: bool = False) -> jax.Array:
    """Decode attention straight off a packed Iris KV stream.

    ``kvc`` is a :class:`repro.kvcache.PackedKVCache`; ``q``:
    ``(B, 1, H, hd)``; ``pos`` / ``slot_ids``: ``(B,)``.  The default
    path runs the stream-direct Pallas kernel (packed pages ->
    registers -> dot, no dense K/V intermediate); ``oracle=True``
    materializes the dequantized dense K/V and reuses
    :func:`decode_attention` — bit-identical by construction, kept as
    the verification path.
    """
    if oracle:
        kf, vf = kvc.dense_kv(layer, slot_ids)
        return decode_attention(q, kf, vf, pos)
    from repro.kvcache.kernels import stream_attention_cache  # lazy

    return stream_attention_cache(kvc, q, pos, slot_ids, layer=layer)


# ----------------------------------------------------------------------
# attention block entry points
# ----------------------------------------------------------------------
def attention_block(cfg: ModelConfig, p: dict, x: jax.Array,
                    positions: jax.Array, inv_freq,
                    causal: bool = True,
                    kv_override: tuple[jax.Array, jax.Array] | None = None
                    ) -> jax.Array:
    """Full-sequence attention (train/prefill or encoder/cross)."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _project(cfg, p, x, "q").reshape(b, s, h, hd)
    if kv_override is None:
        k = _project(cfg, p, x, "k").reshape(b, s, hkv, hd)
        v = _project(cfg, p, x, "v").reshape(b, s, hkv, hd)
        q = apply_rope(q, positions, inv_freq, cfg.mrope_sections)
        k = apply_rope(k, positions, inv_freq, cfg.mrope_sections)
    else:
        k, v = kv_override                       # cross-attention memory
    out = flash_attention(q, k, v, causal=causal)
    return _project(cfg, p, out.reshape(b, s, h * hd), "o")


def attention_decode_block(cfg: ModelConfig, p: dict, x: jax.Array,
                           k_cache: jax.Array, v_cache: jax.Array,
                           pos: jax.Array, inv_freq
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token step; pos: (B,) per-row write positions.
    Returns (out, new_k_cache, new_v_cache)."""
    b, _, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _project(cfg, p, x, "q").reshape(b, 1, h, hd)
    k = _project(cfg, p, x, "k").reshape(b, 1, hkv, hd)
    v = _project(cfg, p, x, "v").reshape(b, 1, hkv, hd)
    pos_b = pos[:, None]                                 # (B, 1)
    q = apply_rope(q, pos_b, inv_freq, cfg.mrope_sections)
    k = apply_rope(k, pos_b, inv_freq, cfg.mrope_sections)
    rows = jnp.arange(b)
    k_cache = k_cache.at[rows, pos].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[rows, pos].set(v[:, 0].astype(v_cache.dtype))
    out = decode_attention(q, k_cache, v_cache, pos)
    y = _project(cfg, p, out.reshape(b, 1, h * hd), "o")
    return y, k_cache, v_cache


def cross_kv(cfg: ModelConfig, p: dict, memory: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """Project encoder memory (B, ctx, d) to cross K/V (B, ctx, Hkv, hd)."""
    b, s, _ = memory.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = _project(cfg, p, memory, "k").reshape(b, s, hkv, hd)
    v = _project(cfg, p, memory, "v").reshape(b, s, hkv, hd)
    return k, v


def cross_attention_block(cfg: ModelConfig, p: dict, x: jax.Array,
                          memory: jax.Array | None = None,
                          kv: tuple[jax.Array, jax.Array] | None = None
                          ) -> jax.Array:
    """Decoder cross-attention; pass encoder ``memory`` (train) or
    precomputed ``kv`` (decode)."""
    if kv is None:
        kv = cross_kv(cfg, p, memory)
    return attention_block(cfg, p, x, positions=None, inv_freq=None,
                           causal=False, kv_override=kv)
