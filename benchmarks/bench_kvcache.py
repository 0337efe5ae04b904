"""Packed KV-cache bench: stream-direct decode attention vs dense KV.

The ISSUE-10 acceptance measurement, on the reduced smollm geometry:

* **bit-identity gate** — engine decode on the Iris-packed KV cache
  with the stream-direct attention kernel must emit, bit for bit, the
  tokens of the materialized dense-dequant oracle over the same pages
  (int3 and int4, ragged admission).  The bench exits nonzero on any
  mismatch.
* **planner accounting** — the per-page layout is planned once; every
  append across layers / slots / pages / steps reuses it (scheduler-run
  and cache-hit counters recorded, re-plans are a hard failure).
* **bandwidth model** — resident KV bytes and per-token decode-read
  bytes for the packed pages vs a bf16 dense cache, plus the planned
  layout's bus efficiency ``B_eff`` (the paper's figure of merit).
* **microbench** — interpret-mode wall clock for append and for
  stream-kernel vs dense-oracle attention (functional sanity numbers,
  not device truth).

Written into ``BENCH_kvcache.json`` at the repo root.

CLI:  PYTHONPATH=src python benchmarks/bench_kvcache.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time


def _mean_us(fn, repeats: int) -> float:
    fn()                                    # warm (trace + lower)
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats * 1e6


def run(quick: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.configs import get_config
    from repro.core.iris import DEFAULT_CACHE
    from repro.engine import Engine, EngineConfig, EngineRequest, \
        PackedAdapter
    from repro.kvcache import PackedKVCache
    from repro.models.attention import decode_attention
    from repro.models.model import Model
    from repro.quant import QuantSpec

    cfg = get_config("smollm-135m").reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=128)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    trees = {bits: api.pack_tree(cfg, params,
                                 QuantSpec(bits=bits, group_size=32), m=512)
             for bits in (3, 4)}
    batch, max_seq, page_tokens = 2, 32, 8

    # -- bit-identity gate: stream kernel vs dense-dequant oracle --------
    def serve(tree, kv_attention):
        reqs = [EngineRequest(uid=0, prompt=[5, 9], max_new_tokens=2),
                EngineRequest(uid=1, prompt=[17, 3, 8], max_new_tokens=3),
                EngineRequest(uid=2, prompt=[40], max_new_tokens=2)]
        eng = Engine(PackedAdapter(cfg, tree, kv="packed",
                                   kv_attention=kv_attention,
                                   page_tokens=page_tokens),
                     EngineConfig(batch_size=batch, max_seq=max_seq))
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return [r.generated for r in reqs], eng

    identity = {}
    for bits, tree in trees.items():
        stream_toks, eng = serve(tree, "stream")
        misses0 = DEFAULT_CACHE.misses
        dense_toks, _ = serve(tree, "dense")
        ok = stream_toks == dense_toks
        kvc = eng.state["packed_kv"]
        identity[f"int{bits}"] = {
            "tokens": sum(len(t) for t in stream_toks),
            "identical": bool(ok),
            "plan_stats": dict(kvc.plan_stats),
            "appends_replanned": DEFAULT_CACHE.misses != misses0,
        }
        print(f"kvcache/bit_identity_int{bits},0.0,"
              f"tokens={identity[f'int{bits}']['tokens']};identical={ok};"
              f"scheduler_runs={kvc.plan_stats.get('scheduler_runs')}",
              flush=True)

    # -- bandwidth model: packed pages vs dense bf16 cache ---------------
    bandwidth = {}
    for bits in (3, 4):
        kvc = PackedKVCache.create(cfg, bits=bits, page_tokens=page_tokens,
                                   n_slots=batch, max_seq=max_seq)
        eff = float(kvc.layout.metrics().efficiency)
        packed_bytes = kvc.stream_bytes()
        dense_bytes = (cfg.n_layers * batch * max_seq * cfg.n_kv_heads
                       * cfg.head_dim * 2 * 2)        # bf16, K and V
        # one decode step reads every resident token's K and V once
        per_tok_packed = packed_bytes / (cfg.n_layers * batch * kvc.smax)
        per_tok_dense = dense_bytes / (cfg.n_layers * batch * max_seq)
        bandwidth[f"int{bits}"] = {
            "b_eff": eff,
            "resident_bytes_packed": packed_bytes,
            "resident_bytes_dense_bf16": dense_bytes,
            "decode_read_bytes_per_token_packed": per_tok_packed,
            "decode_read_bytes_per_token_dense_bf16": per_tok_dense,
            "bytes_ratio": dense_bytes / packed_bytes,
        }
        print(f"kvcache/bandwidth_int{bits},0.0,"
              f"B_eff={eff:.3f};ratio={dense_bytes / packed_bytes:.2f};"
              f"packed_B={packed_bytes};dense_B={dense_bytes}", flush=True)

    # -- microbench: append + attention paths ----------------------------
    from repro.kvcache.kernels import stream_attention_cache

    reps = 2 if quick else 5
    rng = np.random.default_rng(0)
    kvc = PackedKVCache.create(cfg, bits=4, page_tokens=page_tokens,
                               n_slots=batch, max_seq=max_seq)
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = jnp.asarray(rng.normal(size=(batch, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(batch, hkv, hd)), jnp.float32)
    slots = jnp.arange(batch)
    for t in range(6):
        kvc = kvc.append(k, v, jnp.full((batch,), t, jnp.int32), slots,
                         layer=0)
    us_append = _mean_us(
        lambda: jax.block_until_ready(kvc.append(
            k, v, jnp.full((batch,), 6, jnp.int32), slots, layer=0).pages),
        reps)

    pos = jnp.full((batch,), 5, jnp.int32)
    q = jnp.asarray(rng.normal(size=(batch, 1, cfg.n_heads, hd)),
                    jnp.bfloat16)
    us_stream = _mean_us(
        lambda: jax.block_until_ready(stream_attention_cache(
            kvc, q, pos, slots, layer=0)), reps)
    us_dense = _mean_us(
        lambda: jax.block_until_ready(decode_attention(
            q, *kvc.dense_kv(0, slots), pos)), reps)
    got = stream_attention_cache(kvc, q, pos, slots, layer=0)
    want = decode_attention(q, *kvc.dense_kv(0, slots), pos)
    kernel_identical = bool(
        (np.asarray(got).view(np.uint16) ==
         np.asarray(want).view(np.uint16)).all())
    from repro.kernels.backend import interpret_mode

    micro = {
        "interpret": interpret_mode(),
        "append_us": us_append,
        "stream_attention_us": us_stream,
        "dense_oracle_attention_us": us_dense,
        "kernel_bit_identical": kernel_identical,
    }
    print(f"kvcache/append,{us_append:.1f},interpret={interpret_mode()}",
          flush=True)
    print(f"kvcache/stream_attention,{us_stream:.1f},"
          f"dense_oracle_us={us_dense:.1f};identical={kernel_identical}",
          flush=True)

    out = {
        "quick": quick,
        "config": {
            "arch": cfg.name, "batch_size": batch, "max_seq": max_seq,
            "page_tokens": page_tokens, "n_layers": cfg.n_layers,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        },
        "bit_identity": identity,
        "bandwidth": bandwidth,
        "microbench": micro,
    }
    path = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_kvcache.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    if not all(v["identical"] for v in identity.values()) \
            or not kernel_identical:
        raise SystemExit(
            "kvcache bench: stream-direct attention is NOT bit-identical "
            "to the dense-KV oracle")
    if any(v["appends_replanned"] for v in identity.values()):
        raise SystemExit("kvcache bench: an append re-planned the layout")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
