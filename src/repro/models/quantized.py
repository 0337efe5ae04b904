"""Quantized decode path: serve with Iris-organized packed weights.

End-to-end instantiation of the paper for LM serving (dense-family archs):

1. ``repro.api.pack_tree`` quantizes every per-layer weight matrix to
   intN (group scales), plans the per-layer Iris stream layout and packs
   both the unified HBM stream buffers and the lane-packed uint32 kernel
   views into one :class:`~repro.tree.PackedTree` pytree;
2. ``packed_decode_step`` consumes the tree's kernel views directly via
   the dequant-on-load Pallas matmul (``kernels.packed_matmul``) — dense
   bf16 weights never exist in memory.

This module owns only the *decode math*; all pack/plan wiring lives
behind ``repro.api.pack_tree``.  ``PackedParams`` and
``quantize_params`` survive as deprecated aliases of the new surface.
Exercised by examples/packed_serving.py and
tests/test_quantized_serving.py, with bytes-moved accounting vs the bf16
and padded-int baselines.
"""
from __future__ import annotations

import warnings
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:  # pragma: no cover
    from repro.tree import PackedTree

from repro.configs.base import ModelConfig
from repro.engine.trace import span
from repro.kernels.packed_matmul import packed_matmul
from repro.quant.qtypes import QuantSpec

from .layers import activation, apply_norm, rope_freqs
from .transformer import n_periods, period_template


def quantizable(cfg: ModelConfig) -> bool:
    """The packed decode path covers the dense sublayer template."""
    t = period_template(cfg)
    return (len(t) == 1 and t[0].mixer == "attn" and t[0].ffn == "mlp"
            and not t[0].cross)


def quantize_params(cfg: ModelConfig, params: dict, spec: QuantSpec):
    """Deprecated: use :func:`repro.api.pack_tree`.

    Thin wrapper kept for pre-``PackedTree`` callers; returns a
    :class:`~repro.tree.PackedTree` (field-compatible with the old
    ``PackedParams``: ``.packed`` / ``.scales`` / ``.other`` / ``.spec``
    / ``.shapes``), built without stream buffers.
    """
    warnings.warn(
        "quantize_params is deprecated; use repro.api.pack_tree(cfg, "
        "params, spec), which also plans and packs the Iris stream "
        "buffers", DeprecationWarning, stacklevel=2,
    )
    from repro import api

    return api.pack_tree(cfg, params, spec, with_streams=False)


def __getattr__(name: str):
    if name == "PackedParams":
        # deprecated alias of the pytree front door
        from repro.tree import _warn_packed_params

        return _warn_packed_params()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


#: matmul tiles: K in 512-row steps, N in 128-lane columns
_TILE_K, _TILE_N = 512, 128


def _blocks(k: int, n: int, group_size: int) -> dict[str, int]:
    """Tile-legal blocks for a ``(K, N)`` decode matmul.

    A dimension the tile divides is tiled; otherwise its block is the
    whole dimension (always legal on a TPU).  ``block_k`` stays a
    multiple of ``group_size``: the tile only when the group divides it,
    and K itself is a group multiple.  At smollm-135m widths this gives
    K=576 -> 576, K=1536 -> 512, N=192/576 -> whole, N=1536 -> 128.
    """
    bk = _TILE_K if k % _TILE_K == 0 and _TILE_K % group_size == 0 else k
    bn = _TILE_N if n % _TILE_N == 0 else n
    return {"block_k": bk, "block_n": bn}


def _pad_rows(x2d):
    """Pad the batch rows to a power-of-two MXU tile (at least 8)."""
    b = x2d.shape[0]
    bm = max(8, 1 << (b - 1).bit_length())
    if bm != b:
        x2d = jnp.pad(x2d, ((0, bm - b), (0, 0)))
    return x2d, bm


def _pmm(x2d, pw, sc, spec):
    """x2d: (B, K) @ packed (K*bits/32, N) -> (B, N)."""
    b, k = x2d.shape
    x2d, bm = _pad_rows(x2d)
    out = packed_matmul(
        x2d, pw, sc, bits=spec.bits, group_size=spec.group_size,
        block_m=bm, **_blocks(k, pw.shape[1], spec.group_size))
    return out[:b]


def _pmm_direct(x2d, pp, name, layer, words=None):
    """Stream-direct twin of :func:`_pmm`: same B padding and block
    choices, but the weights are gathered straight from the layer's
    packed Iris stream (``kernels.stream_matmul``) — no lane-packed
    kernel view, no dense intermediate, any element width <= 32.
    ``words`` optionally supplies the layer's stream word view from an
    external stage (see :meth:`~repro.tree.PackedTree.matmul_direct`)."""
    b, k = x2d.shape
    x2d, bm = _pad_rows(x2d)
    out = pp.matmul_direct(
        x2d, name, layer, words=words, block_m=bm,
        **_blocks(k, pp.shapes[name][1], pp.spec.group_size))
    return out[:b]


def packed_decode_step(cfg: ModelConfig, pp: "PackedTree", state: dict,
                       tokens: jax.Array, *,
                       weights: str = "auto", slot_ids=None,
                       stream_source=None, kv: str = "dense",
                       kv_attention: str = "stream"
                       ) -> tuple[jax.Array, dict]:
    """One decode token with dequant-on-load weights (dense archs).

    ``pp`` is the :class:`~repro.tree.PackedTree` built by
    ``repro.api.pack_tree``.  Mirrors Model.decode_step but every large
    matmul reads packed codes.

    ``weights`` selects the matmul operand source: ``"packed"`` reads
    the lane-packed kernel views (two-pass legacy path, bits in
    ``SUPPORTED_BITS`` only), ``"stream"`` gathers straight from the
    per-layer Iris stream buffers (stream-direct, any bits <= 32),
    ``"auto"`` uses the kernel views when the tree has them and falls
    back to stream-direct otherwise — which is how int3/int5/int6/int7
    trees serve end-to-end.

    ``slot_ids`` enables ragged-M stepping for the continuous-batching
    engine: an int array of the *active* cache rows, aligned with
    ``tokens`` (shape ``(M,)`` for M active slots, M <= cache batch).
    Only those rows' KV entries and clocks advance; matmul M equals the
    active count (padded to the kernel tile internally), so half-empty
    batches cost half-size matmuls.  ``None`` keeps the legacy
    full-batch semantics (``tokens`` spans every cache row and every
    row's clock ticks).  Because every per-row computation is
    independent, a row's results are bit-identical either way.

    ``stream_source`` (stream path only) maps a layer index to that
    layer's uint32 stream word view — e.g. a
    :class:`~repro.engine.streams.StreamUploader` staging host->device
    uploads ahead of compute.  ``None`` reads the tree's resident
    buffers.

    ``kv`` selects the cache representation: ``"dense"`` keeps the
    legacy bf16 ``k_cache`` / ``v_cache`` tensors; ``"packed"`` streams
    K/V through the Iris-planned :class:`~repro.kvcache.PackedKVCache`
    carried in ``state["packed_kv"]`` — appends write packed token
    pages, and attention consumes them via the stream-direct Pallas
    kernel (``kv_attention="stream"``) or the materialized dequant
    oracle (``kv_attention="dense"``, bit-identical by construction).
    """
    from . import attention as attn

    if weights not in ("auto", "packed", "stream"):
        raise ValueError(
            f"weights must be 'auto', 'packed' or 'stream'; got {weights!r}"
        )
    if kv not in ("dense", "packed"):
        raise ValueError(f"kv must be 'dense' or 'packed'; got {kv!r}")
    if kv_attention not in ("stream", "dense"):
        raise ValueError(
            f"kv_attention must be 'stream' or 'dense'; got {kv_attention!r}"
        )
    kvc = None
    if kv == "packed":
        kvc = state.get("packed_kv")
        if kvc is None:
            raise ValueError(
                "kv='packed' needs a PackedKVCache in state['packed_kv'] "
                "(see repro.kvcache.PackedKVCache.create)"
            )
    use_stream = weights == "stream" or (weights == "auto" and not pp.packed)
    if weights == "packed" and not pp.packed:
        raise ValueError(
            "tree has no lane-packed kernel views (built with "
            "with_kernel_views=False); serve with weights='stream'"
        )
    if use_stream and pp.streams is None and stream_source is None:
        raise ValueError(
            "tree has no stream buffers (built with with_streams=False); "
            "serve with weights='packed' or supply stream_source"
        )
    if stream_source is not None and not use_stream:
        raise ValueError(
            "stream_source only applies to the stream-direct path "
            "(weights='stream', or 'auto' on a kernel-view-free tree)"
        )
    spec = pp.spec
    b = tokens.shape[0]
    if slot_ids is not None and slot_ids.shape[0] != b:
        raise ValueError(
            f"slot_ids has {slot_ids.shape[0]} rows but tokens has {b}"
        )
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with span("repro.model.embed"):
        inv_freq = rope_freqs(cfg)
        rows = jnp.arange(b) if slot_ids is None else slot_ids
        pos = state["pos"] if slot_ids is None else state["pos"][rows]
        x = jnp.take(pp.other["embed"], tokens, axis=0) \
            * jnp.asarray(cfg.d_model ** 0.5, pp.other["embed"].dtype)

    def mm(name, period, x2d, words=None):
        if use_stream:
            return _pmm_direct(x2d.astype(jnp.float32), pp, name, period,
                               words=words)
        return _pmm(x2d.astype(jnp.float32), pp.packed[name][period],
                    pp.scales[name][period], spec)

    np_ = n_periods(cfg)
    k_cache, v_cache = state["k_cache"], state["v_cache"]
    new_k, new_v = [], []
    for layer in range(np_):
        words = stream_source(layer) if stream_source is not None else None
        with span("repro.layer.qkv", layer=layer):
            hnorm = apply_norm(cfg, jax.tree.map(lambda a: a[layer],
                                                 pp.other["norm1"]), x)
            q = mm("attn/wq", layer, hnorm, words).reshape(b, 1, h, hd)
            kk = mm("attn/wk", layer, hnorm, words).reshape(b, 1, hkv, hd)
            vv = mm("attn/wv", layer, hnorm, words).reshape(b, 1, hkv, hd)
            if cfg.use_bias:
                q = q + pp.other["attn/bq"][layer].reshape(1, 1, h, hd)
                kk = kk + pp.other["attn/bk"][layer].reshape(1, 1, hkv, hd)
                vv = vv + pp.other["attn/bv"][layer].reshape(1, 1, hkv, hd)
            pos_b = pos[:, None]
            q = attn.apply_rope(q, pos_b, inv_freq, cfg.mrope_sections)
            kk = attn.apply_rope(kk, pos_b, inv_freq, cfg.mrope_sections)
        with span("repro.layer.kv_write", layer=layer):
            if kvc is not None:
                kvc = kvc.append(kk[:, 0], vv[:, 0], pos, rows, layer=layer)
            else:
                kc = k_cache[layer].at[rows, pos].set(
                    kk[:, 0].astype(k_cache.dtype))
                vc = v_cache[layer].at[rows, pos].set(
                    vv[:, 0].astype(v_cache.dtype))
                new_k.append(kc)
                new_v.append(vc)
        with span("repro.layer.attend", layer=layer):
            if kvc is not None:
                att = attn.stream_decode_attention(
                    kvc, q.astype(jnp.bfloat16), pos, rows, layer=layer,
                    oracle=kv_attention == "dense")
            else:
                att = attn.decode_attention(q.astype(jnp.bfloat16),
                                            kc[rows], vc[rows], pos)
            y = mm("attn/wo", layer, att.reshape(b, h * hd), words)
            if cfg.use_bias:
                y = y + pp.other["attn/bo"][layer]
            x = x + y.astype(x.dtype)
        with span("repro.layer.mlp", layer=layer):
            h2 = apply_norm(cfg, jax.tree.map(lambda a: a[layer],
                                              pp.other["norm2"]), x)
            g = mm("mlp/w_gate", layer, h2, words)
            u = mm("mlp/w_up", layer, h2, words)
            if cfg.use_bias:
                g = g + pp.other["mlp/b_gate"][layer]
                u = u + pp.other["mlp/b_up"][layer]
            hh = activation(cfg.act, g) * u
            y2 = mm("mlp/w_down", layer, hh, words)
            if cfg.use_bias:
                y2 = y2 + pp.other["mlp/b_down"][layer]
            x = x + y2.astype(x.dtype)

    with span("repro.model.head"):
        x = apply_norm(cfg, pp.other["final_norm"], x)
        if cfg.tie_embeddings:
            logits = x @ pp.other["embed"].T
        else:
            logits = x @ pp.other["unembed"]
    with span("repro.model.state"):
        new_state = dict(state)
        if kvc is not None:
            new_state["packed_kv"] = kvc
        else:
            new_state["k_cache"] = jnp.stack(new_k)
            new_state["v_cache"] = jnp.stack(new_v)
        if slot_ids is None:
            new_state["pos"] = pos + 1
        else:
            new_state["pos"] = state["pos"].at[rows].add(1)
    return logits, new_state


def bytes_per_token_report(cfg: ModelConfig, pp: "PackedTree") -> dict:
    """Weight bytes streamed per decode token: packed vs baselines."""
    n_elems = sum(int(jnp.prod(jnp.array(s)) * n_periods(cfg))
                  for s in pp.shapes.values())
    if pp.packed:
        packed_b = pp.hbm_bytes()
    else:
        # stream-direct tree: the per-layer Iris stream *is* the serving
        # weight storage (scales ride inside it)
        packed_b = pp.stream_bytes + sum(
            int(jnp.size(x)) * x.dtype.itemsize
            for x in jax.tree.leaves(pp.other))
    pad_bits = 8 if pp.spec.bits > 4 else (4 if pp.spec.bits > 2 else 2)
    pad_bits = max(pad_bits, 1 << (pp.spec.bits - 1).bit_length())
    return {
        "packed_MiB": packed_b / 2**20,
        "bf16_MiB": (n_elems * 2
                     + sum(int(x.size) * x.dtype.itemsize
                           for x in jax.tree.leaves(pp.other))) / 2**20,
        "padded_int_MiB": (n_elems * pad_bits / 8) / 2**20,
        "quantized_elems": n_elems,
    }
