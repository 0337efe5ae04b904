"""Benchmark harness: one function per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV rows.

  bench_example_layout   — paper §4 worked example (Figs. 3-5)
  bench_inv_helmholtz    — paper Table 6 (delta/W sweep)
  bench_matmul_widths    — paper Table 7 (custom-width sweep)
  bench_decode_module    — paper Listing 2 / §5 (decode-unit resources)
  bench_pack_throughput  — paper Listing 1 (host-side organization)
  bench_decode_kernel    — Pallas decode kernel vs numpy oracle
  bench_packed_matmul    — dequant-on-load matmul kernel vs oracle
  bench_model_packing    — Iris parameter streaming per architecture
  bench_scheduler_scale  — Iris runtime scaling (interval mode)
  bench_scheduler_throughput — unified engine: interval vs cycle on a
                           1M-cycle problem (bit-identical), layout-cache
                           hit vs miss, schedule_many batch dedupe
  bench_exec             — compiled execution plans vs the per-slot
                           legacy paths on the §4 LM layer bundle; also
                           writes machine-readable BENCH_exec.json at the
                           repo root and exits nonzero if the compiled
                           paths are not bit-identical to the legacy ones
                           (see bench_plan.py)
  bench_plan             — planner scale-out: cold vs parallel vs
                           incremental vs persistent planning on a
                           16-unique-signature stack + host vs device
                           pack; writes BENCH_plan.json and exits
                           nonzero on any bit-equivalence mismatch
                           (see bench_plan.py)
  bench_stream_matmul    — stream-direct matmul (decode fused into the
                           compute prologue) vs the two-pass path on the
                           int3 LM layer bundle; writes
                           BENCH_stream_mm.json (see bench_stream_mm.py)
  bench_serve            — serving engine: static vs continuous batching
                           latency/goodput sweep + bit-identity vs the
                           single-stream loop on the int3 smollm tree;
                           writes BENCH_serve.json (see bench_serve.py)
  bench_kvcache          — packed KV-cache streams: stream-direct decode
                           attention vs the dense-dequant oracle
                           (bit-identity gated), append-never-replans
                           accounting, KV bandwidth model; writes
                           BENCH_kvcache.json (see bench_kvcache.py)

CLI:  python benchmarks/run.py [--quick] [--only SUBSTR]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

#: set by --quick: smaller problem sizes, fewer repeats (CI smoke mode)
QUICK = False


def _timeit(fn, repeats: int = 5, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats * 1e6  # us


def _timeit_min(fn, repeats: int = 5, warmup: int = 1) -> float:
    """Best-of-N in us — robust to the scheduler noise mean-of-N absorbs."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


# ----------------------------------------------------------------------
# Paper tables/figures — everything below drives the repro.api façade;
# the strategy loop iterates the registry instead of importing one
# function per layout family.  ``cache=None`` keeps the timings honest
# (a warm DEFAULT_CACHE would turn re-schedules into lookups).
# ----------------------------------------------------------------------
def bench_example_layout() -> None:
    from repro import api

    for label in api.strategies():
        us = _timeit(lambda label=label:
                     api.plan(api.PAPER_EXAMPLE, label, cache=None).layout)
        m = api.plan(api.PAPER_EXAMPLE, label, cache=None).metrics
        _row(f"example/{label}", us,
             f"C_max={m.c_max};L_max={m.l_max};B_eff={m.efficiency:.3f}")


def bench_inv_helmholtz() -> None:
    from repro import api
    from repro.api import INV_HELMHOLTZ, make_problem

    m = api.plan(INV_HELMHOLTZ, "homogeneous").metrics
    us = _timeit(lambda:
                 api.plan(INV_HELMHOLTZ, "homogeneous", cache=None).layout)
    fifo = sum(m.fifo_depth.values())
    _row("helmholtz/naive", us,
         f"C_max={m.c_max};L_max={m.l_max};B_eff={m.efficiency:.3f};"
         f"fifo={fifo}")
    for dw in (4, 3, 2, 1):
        p = make_problem(256, [(a.name, a.width, a.depth, a.due)
                               for a in INV_HELMHOLTZ.arrays], max_lanes=dw)
        us = _timeit(lambda p=p: api.plan(p, cache=None).layout)
        m = api.plan(p, cache=None).metrics
        fifo = sum(m.fifo_depth.values())
        _row(f"helmholtz/iris_dw{dw}", us,
             f"C_max={m.c_max};L_max={m.l_max};B_eff={m.efficiency:.3f};"
             f"fifo={fifo}")


def bench_matmul_widths() -> None:
    from repro import api
    from repro.api import matmul_problem

    for wa, wb in ((64, 64), (33, 31), (30, 19)):
        p = matmul_problem(wa, wb)
        for label, strat in (("naive", "homogeneous"), ("iris", "iris")):
            us = _timeit(lambda p=p, s=strat:
                         api.plan(p, s, cache=None).layout)
            m = api.plan(p, strat, cache=None).metrics
            fifo = sum(m.fifo_depth.values())
            _row(f"matmul_w{wa}x{wb}/{label}", us,
                 f"C_max={m.c_max};L_max={m.l_max};"
                 f"B_eff={m.efficiency:.3f};fifo={fifo}")


def bench_decode_module() -> None:
    """Listing 2 analogue: decode units, staging and ports per layout."""
    from repro import api
    from repro.api import PAPER_EXAMPLE, matmul_problem
    from repro.core.codegen import decode_plan

    for label, prob in (("example", PAPER_EXAMPLE),
                        ("matmul_33x31", matmul_problem(33, 31))):
        for kind, strat in (("iris", "iris"), ("naive", "homogeneous")):
            pl = api.plan(prob, strat)
            us = _timeit(lambda lay=pl.layout: decode_plan(lay))
            c_lines = len(pl.emit(target="c").splitlines())
            _row(f"decode_module/{label}/{kind}", us,
                 f"units={pl.decode_plan.n_units};"
                 f"fifo={sum(pl.decode_plan.fifo_depths.values())};"
                 f"ports={sum(pl.decode_plan.write_ports.values())};"
                 f"c_lines={c_lines}")


def bench_pack_throughput() -> None:
    from repro import api

    p = api.make_problem(256, [("w", 4, 65536, 10), ("s", 16, 4096, 10),
                               ("n", 16, 1024, 0), ("b", 32, 512, 20)])
    pl = api.plan(p)
    codes = api.random_codes(p)
    us = _timeit(lambda: pl.pack(codes), repeats=3)
    total_bytes = p.p_tot / 8
    _row("pack/host_throughput", us,
         f"MBps={total_bytes / us:.1f};bytes={int(total_bytes)}")


def bench_decode_kernel() -> None:
    from repro import api

    p = api.make_problem(128, [("q", 4, 8192, 4), ("s", 16, 512, 4),
                               ("b", 32, 128, 8)])
    pl = api.plan(p)
    buf = pl.pack(api.random_codes(p))
    us_k = _timeit(lambda: pl.decode(buf, backend="pallas"),
                   repeats=2)
    us_r = _timeit(lambda: pl.decode(buf, backend="numpy"), repeats=2)
    _row("decode_kernel/pallas_interpret", us_k, f"oracle_us={us_r:.1f}")


def bench_packed_matmul() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.packed_matmul import packed_matmul
    from repro.kernels.ref import packed_matmul_ref
    from repro.quant import QuantSpec, pack_codes_u32, quantize

    for bits in (4, 8):
        m, k, n = 64, 1024, 256
        w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32)
        qt = quantize(w, QuantSpec(bits=bits, group_size=128))
        pw = pack_codes_u32(qt.codes, bits)
        x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)

        def run(bits=bits, pw=pw, qt=qt, x=x):
            packed_matmul(x, pw, qt.scales, bits=bits, group_size=128,
                          block_m=64, block_k=256).block_until_ready()

        us = _timeit(run, repeats=2)
        ref = packed_matmul_ref(x, pw, qt.scales, bits=bits, group_size=128)
        got = packed_matmul(x, pw, qt.scales, bits=bits, group_size=128,
                            block_m=64, block_k=256)
        err = float(jnp.abs(got - ref).max())
        packed_bytes = pw.size * 4 + qt.scales.size * 2
        dense_bytes = k * n * 2
        _row(f"packed_matmul/int{bits}", us,
             f"max_err={err:.2e};bytes_ratio={dense_bytes/packed_bytes:.2f}")


def bench_ssd_scan_kernel() -> None:
    """Pallas chunked linear-attention kernel vs the pure-JAX recurrence
    (the §Perf iterD5 lever for SSM/hybrid training memory)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.linear_scan import ssd_scan
    from repro.models.linear_attention import recurrent_scan

    b, t, h, d = 2, 256, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32) * 0.5
    logw = -jax.nn.softplus(
        jax.random.normal(ks[3], (b, t, h), jnp.float32))

    def run_kernel():
        ssd_scan(q, k, v, logw, chunk=128).block_until_ready()

    def run_ref():
        recurrent_scan(q, k, v, logw[..., None],
                       rwkv_mode=False)[0].block_until_ready()

    us_k = _timeit(run_kernel, repeats=2)
    us_r = _timeit(run_ref, repeats=2)
    got = ssd_scan(q, k, v, logw, chunk=128)
    want, _ = recurrent_scan(q, k, v, logw[..., None], rwkv_mode=False)
    err = float(jnp.abs(got - want).max())
    # HBM state traffic per chunk: pure-JAX round-trips the f32 state
    # every mini-chunk; the kernel keeps it in VMEM scratch
    state_traffic_ref = (t // 32) * 2 * b * h * d * d * 4
    _row("ssd_scan/pallas_vs_recurrence", us_k,
         f"ref_us={us_r:.1f};max_err={err:.2e};"
         f"ref_state_hbm_bytes={state_traffic_ref};kernel_state_hbm_bytes=0")


def bench_model_packing() -> None:
    from repro.configs import ARCH_IDS, get_config
    from repro.core.packing import serving_stream_report
    from repro.quant import QuantSpec

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for bits in (3, 4):
            t0 = time.perf_counter()
            r = serving_stream_report(cfg, QuantSpec(bits=bits,
                                                     group_size=128))
            us = (time.perf_counter() - t0) * 1e6
            _row(f"model_packing/{arch}/int{bits}", us,
                 f"iris_MiB={r['iris_MiB_per_layer']:.1f};"
                 f"pad_MiB={r['padded_MiB_per_layer']:.1f};"
                 f"bf16_MiB={r['bf16_MiB_per_layer']:.1f};"
                 f"B_eff={r['iris_efficiency']:.4f};"
                 f"Lmax_iris={r['iris_L_max']};"
                 f"Lmax_hom={r['homogeneous_unit_L_max']};"
                 f"fifo_iris={r['iris_unit_fifo']};"
                 f"fifo_hom={r['homogeneous_unit_fifo']}")


def bench_scheduler_scale() -> None:
    # engine-level microbench: deliberately below the façade
    from repro.api import make_problem
    from repro.core.iris import schedule

    rng = np.random.default_rng(0)
    for n_arrays, depth in ((8, 1000), (16, 10_000), (32, 100_000)):
        specs = [(f"a{i}", int(rng.integers(3, 33)),
                  int(rng.integers(depth // 2, depth)),
                  int(rng.integers(0, 64))) for i in range(n_arrays)]
        p = make_problem(512, specs)
        us = _timeit(lambda p=p: schedule(p, mode="interval"), repeats=2)
        lay = schedule(p, mode="interval")
        _row(f"scheduler/interval_n{n_arrays}_d{depth}", us,
             f"C_max={lay.c_max};intervals={len(lay.intervals())};"
             f"B_eff={lay.metrics().efficiency:.4f}")


def bench_scheduler_throughput() -> None:
    """Unified-engine throughput: the ISSUE-1 acceptance benchmark.

    (a) a 1M-cycle lane-capped problem (paper Table 6's delta/W knob at
        model-packing scale): event-driven interval mode vs per-cycle
        replay, asserting the layouts are bit-identical;
    (b) an LRM-contended multi-release problem: layout-cache miss vs hit
        (the serving hot path — repeated identical problems);
    (c) schedule_many over a uniform 32-layer stack: one scheduler run,
        31 rebinds.
    """
    # engine-level microbench: deliberately below the façade
    from repro.api import make_problem
    from repro.core.iris import LayoutCache, schedule, schedule_many

    # (a) every task runs at its (capped) full rate -> long constant runs
    specs = [(f"a{i}", 8, 7_900_000 + 60_000 * i, 25_000 * i)
             for i in range(8)]
    p_big = make_problem(512, specs, max_lanes=8)
    t0 = time.perf_counter()
    lay_i = schedule(p_big, mode="interval")
    t_interval = time.perf_counter() - t0
    t0 = time.perf_counter()
    lay_c = schedule(p_big, mode="cycle")
    t_cycle = time.perf_counter() - t0
    assert lay_c.count_intervals == lay_i.count_intervals
    _row("scheduler_throughput/1M_interval", t_interval * 1e6,
         f"cycle_us={t_cycle*1e6:.0f};speedup={t_cycle/t_interval:.0f}x;"
         f"C_max={lay_i.c_max};intervals={len(lay_i.intervals())};"
         f"identical=True")

    # (b) contended problem: the expensive case the cache absorbs
    specs = [("a", 7, 15_000_000, 0), ("b", 9, 11_000_000, 120_000),
             ("c", 12, 9_000_000, 300_000), ("d", 17, 6_000_000, 500_000),
             ("e", 23, 4_000_000, 700_000)]
    p_hot = make_problem(512, specs)
    cache = LayoutCache()
    t0 = time.perf_counter()
    schedule(p_hot, mode="interval", cache=cache)
    t_miss = time.perf_counter() - t0
    t0 = time.perf_counter()
    schedule(p_hot, mode="interval", cache=cache)
    t_hit = time.perf_counter() - t0
    _row("scheduler_throughput/cache_hit", t_hit * 1e6,
         f"miss_us={t_miss*1e6:.0f};speedup={t_miss/t_hit:.0f}x;"
         f"C_max={cache.lookup(p_hot).c_max}")

    # (c) uniform stack: every layer is the same scheduling instance
    layers = [make_problem(
        512, [(f"t{j}", 4 + 2 * j, 200_000, 5_000 * j) for j in range(6)])
        for _ in range(32)]
    cache = LayoutCache()
    t0 = time.perf_counter()
    outs = schedule_many(layers, cache=cache)
    t_batch = time.perf_counter() - t0
    _row("scheduler_throughput/batch_32_layers", t_batch * 1e6,
         f"runs={cache.misses};hits={cache.hits};"
         f"C_max={outs[0].c_max}")


def bench_exec() -> None:
    """Compiled exec plans vs per-slot legacy paths + bit-identity gate
    (full bench in bench_plan.py; writes BENCH_exec.json)."""
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from bench_plan import run_exec as _exec_run

    _exec_run(quick=QUICK)


def bench_plan() -> None:
    """Planner scale-out: cold vs parallel vs incremental vs persistent
    planning + host vs device pack, all bit-equivalence gated (full
    bench in bench_plan.py; writes BENCH_plan.json)."""
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from bench_plan import run as _plan_run

    _plan_run(quick=QUICK)


def bench_stream_matmul() -> None:
    """Stream-direct vs two-pass serving on the int3 LM layer bundle
    (full bench in bench_stream_mm.py; writes BENCH_stream_mm.json)."""
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from bench_stream_mm import run as _stream_mm_run

    _stream_mm_run(quick=QUICK)


def bench_serve() -> None:
    """Serving engine: static vs continuous batching + bit-identity gate
    (full bench in bench_serve.py; writes BENCH_serve.json)."""
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from bench_serve import run as _serve_run

    _serve_run(quick=QUICK)


def bench_kvcache() -> None:
    """Packed KV-cache streams: stream-direct attention vs dense oracle
    + append-never-replans gate (full bench in bench_kvcache.py; writes
    BENCH_kvcache.json)."""
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from bench_kvcache import run as _kvcache_run

    _kvcache_run(quick=QUICK)


ALL = [
    bench_example_layout,
    bench_inv_helmholtz,
    bench_matmul_widths,
    bench_decode_module,
    bench_pack_throughput,
    bench_decode_kernel,
    bench_packed_matmul,
    bench_ssd_scan_kernel,
    bench_model_packing,
    bench_scheduler_scale,
    bench_scheduler_throughput,
    bench_exec,
    bench_plan,
    bench_stream_matmul,
    bench_serve,
    bench_kvcache,
]


def main() -> None:
    global QUICK
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / fewer repeats (CI smoke)")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only benches whose name contains SUBSTR")
    args = ap.parse_args()
    QUICK = args.quick
    fns = [f for f in ALL
           if args.only is None or args.only in f.__name__]
    if not fns:
        raise SystemExit(f"no bench matches {args.only!r}")
    print("name,us_per_call,derived")
    for fn in fns:
        fn()


if __name__ == "__main__":
    main()
