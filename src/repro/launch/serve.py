"""Serving launcher CLI (continuous batching; optional Iris-packed path).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --reduced --requests 6 --batch-size 2 --max-new 8 [--packed --bits 8]

Serving runs on :mod:`repro.engine` — the stage-decoupled continuous-
batching engine with bounded admission and per-request metrics.
``--qps`` switches from closed-loop (submit everything, drain) to
open-loop load: requests arrive at the given rate on the wall clock and
queue-time shows up in the metrics.  ``--metrics-out`` writes the
engine's JSON metrics snapshot (schema: DESIGN.md §Serving-engine).

`--packed` serves through the quantized dequant-on-load path for
dense-family archs.  All pack/plan wiring goes through the one front
door — ``repro.api.pack_tree`` — which quantizes the weights, plans the
per-layer Iris stream layouts through the shared layout cache (one
scheduler run for the whole uniform stack; repeated requests with the
same shapes never re-run the scheduler) and packs the unified per-layer
HBM stream buffers.  Lane-packable widths (2/4/8) serve through the
legacy kernel views; every other width (3/5/6/7) serves *stream-direct*
— the Pallas matmul gathers weights straight from the packed stream
(``kernels.stream_matmul``), no dense intermediate — with host->device
uploads double-buffered by :class:`repro.engine.StreamUploader` so the
next layer's transfer overlaps the current layer's compute.

:func:`build_engine` is the construction both this CLI and
``chip_smoke.py`` use.  The Pallas kernels run compiled on a TPU and in
interpret mode elsewhere (:mod:`repro.kernels.backend`); compiled
programs persist in the cache :func:`repro.launch.compile_cache.enable`
sets up.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.kernels.packed_matmul import SUPPORTED_BITS


def _run_open_loop(engine, requests, qps: float,
                   max_steps: int = 100_000) -> None:
    """Submit ``requests`` at ``qps`` arrivals/s (uniform spacing) on the
    wall clock while stepping the engine; drain after the last arrival."""
    t0 = time.monotonic()
    arrivals = [(i / qps, req) for i, req in enumerate(requests)]
    steps = 0
    while arrivals or engine.has_work():
        now = time.monotonic() - t0
        while arrivals and arrivals[0][0] <= now:
            engine.submit(arrivals.pop(0)[1])
        if engine.has_work():
            engine.step()
            steps += 1
            if steps >= max_steps:
                break
        elif arrivals:
            time.sleep(min(0.001, arrivals[0][0] - now))


def build_engine(cfg, model, params, *, packed: bool, bits: int = 8,
                 kv: str = "dense", batch_size: int, max_seq: int,
                 policy: str = "continuous"):
    """Build the serving :class:`~repro.engine.Engine` for ``cfg``.

    ``packed`` serves quantized weights through ``repro.api.pack_tree``
    (``bits`` in 2..8; widths in ``SUPPORTED_BITS`` read the lane-packed
    kernel views, the rest stream-direct); ``kv="packed"`` stores the KV
    cache as packed pages at the weight width.  Prints the packing
    summary lines; the caller closes ``engine.adapter.uploader`` (set
    for stream-direct serving) when done.
    """
    from repro.engine import (
        DenseAdapter,
        Engine,
        EngineConfig,
        PackedAdapter,
        StreamUploader,
    )

    if packed:
        from repro import api
        from repro.models.quantized import bytes_per_token_report, quantizable
        from repro.quant import QuantSpec

        if not quantizable(cfg):
            raise SystemExit(f"{cfg.name}: packed path covers dense archs")
        qspec = QuantSpec(bits=bits, group_size=32)

        # the one front door: quantize -> plan (cached) -> pack streams
        pt = api.pack_tree(cfg, params, qspec)
        rep = bytes_per_token_report(cfg, pt)
        print(f"weight stream/token: packed={rep['packed_MiB']:.2f} MiB "
              f"padded-int={rep['padded_int_MiB']:.2f} "
              f"bf16={rep['bf16_MiB']:.2f} "
              f"({rep['bf16_MiB']/rep['packed_MiB']:.2f}x reduction)")
        print(pt.summary())
        # per-layer plan summary: the shared cache answers by signature,
        # so this never re-runs the scheduler
        print(api.plan(pt.manifest.problem()).summary())

        # compiled execution plan (one per layout signature, shared by
        # every layer through the layout cache): the whole stream decodes
        # with a single fused Pallas kernel per layer
        prog = pt.exec_program()
        print(f"exec program: pieces={prog.n_pieces}, "
              f"kernel lanes={prog.kernel.lanes}, "
              f"host-path arrays={len(prog.host_arrays)}, "
              f"pallas calls/decode={prog.n_pallas_calls}")

        mode = "kernel-views" if pt.packed else "stream-direct"
        # stream-direct serving: double-buffer the per-layer stream
        # uploads so transfer overlaps decode
        uploader = None if pt.packed else StreamUploader(pt)
        print(f"serving path: {mode} (int{bits}, kv={kv})")
        adapter = PackedAdapter(cfg, pt, uploader=uploader, kv=kv)
    else:
        if kv != "dense":
            raise ValueError("packed KV pages need packed=True")
        adapter = DenseAdapter(model, params)

    return Engine(adapter, EngineConfig(
        batch_size=batch_size, max_seq=max_seq, max_backlog=None,
        policy=policy))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--packed", action="store_true")
    # the stream-direct matmul lifts the old lane-packing restriction:
    # any QuantSpec width serves (2/4/8 via kernel views, the rest
    # straight off the Iris stream)
    ap.add_argument("--bits", type=int, default=8,
                    choices=list(range(2, 9)),
                    help="quantization width for --packed; "
                         f"{sorted(SUPPORTED_BITS)} use the lane-packed "
                         "kernel views, other widths serve stream-direct")
    ap.add_argument("--policy", choices=["continuous", "static"],
                    default="continuous",
                    help="slot admission policy (static = drain the whole "
                         "batch before admitting, the baseline)")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="open-loop arrival rate (requests/s); 0 = closed "
                         "loop (submit all up front, drain)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine metrics JSON snapshot here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.engine import EngineRequest
    from repro.launch import compile_cache
    from repro.models.model import Model

    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    engine = build_engine(cfg, model, params, packed=args.packed,
                          bits=args.bits, batch_size=args.batch_size, max_seq=args.max_seq,
                          policy=args.policy)
    uploader = getattr(engine.adapter, "uploader", None)
    requests = []
    for uid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size,
                              rng.integers(2, 6)).tolist()
        requests.append(EngineRequest(uid=uid, prompt=prompt,
                                      max_new_tokens=args.max_new))
    if args.qps > 0:
        _run_open_loop(engine, requests, args.qps)
    else:
        for req in requests:
            engine.submit(req)
        engine.run_until_drained(max_steps=5000)
    stats = engine.stats
    if uploader is not None:
        print(f"stream uploads: {uploader.stats()}")
        uploader.close()
    print(f"completed={stats.completed}/{args.requests} "
          f"steps={stats.steps} tokens={stats.tokens_generated} "
          f"admitted={stats.admitted}")
    snap = engine.metrics.snapshot()
    lat = snap["latency"]["total"]
    thr = snap["throughput"]
    print(f"latency p50={lat['p50_s']*1e3:.1f}ms p99={lat['p99_s']*1e3:.1f}ms"
          f" tokens/s={thr['tokens_per_s']:.1f}"
          f" occupancy={thr['mean_batch_occupancy']:.2f}")
    if args.metrics_out:
        engine.metrics.to_json(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
