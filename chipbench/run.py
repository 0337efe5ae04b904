"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, the chip it runs on, no child processes.  A cell of
``BENCHMARK.json`` names a configuration (``configs/``) and a traffic
mix (``traffic/``).  The run:

1. set-up (``setup_s``, from process start to the window's start):
   weights from the seed on the device, ``build_engine`` (the program's
   front door; ``pack_s``), and two warm-up steps with every slot
   active, so that every program the window runs is compiled (or loaded
   from JAX's persistent cache in ``<checkout>/.jax_cache``);
2. the window: a fresh engine over the warmed adapter, driven by a
   closed loop of one client per slot (``loadgen``), for whole engine
   steps until the first step that ends ``--seconds`` after the start;
3. the device's peak memory, then the program's state is freed and the
   served tokens are compared with the plain reference (``check``).

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the first steps of the window (at least
``TRACE_STEPS`` and ``TRACE_SECONDS``) are traced with ``jax.profiler``
and the result carries the per-layer metrics (``metrics/``), the
device's busy time and a breakdown.  The numbers compared for
``correct`` are printed as the last lines of standard error and, last,
in the result line, which is the last line of standard output.

The result line is strict JSON (no NaN or infinity: a run that would
print one fails instead) and keeps within ``LINE_BYTES``, 8 KiB,
whatever the number of steps, requests or traced events: the window's
steps are summed up in a fixed number of fields (at most ``SLOWEST``
slowest steps and the first ``FIRST_STEPS`` step times), and the
breakdown's lists hold ``tracing.top``'s ten entries, each name cut to
``tracing.NAME_CHARS``.  Every list in the line holds at most 16
entries.  The line grows only with the cell's
metrics and checks, about 70 bytes each.

Exits 1, printing no result, unless JAX finds as many TPU chips as the
cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import spec  # noqa: E402

#: the traced part of a ``--trace 1`` window
TRACE_STEPS = 3
TRACE_SECONDS = 5.0
#: warm-up steps (every slot active; the second reuses freed slots)
WARM_STEPS = 2
#: the result line's budget in bytes
LINE_BYTES = 8192
#: the window's slowest steps and first steps that the result line lists
SLOWEST = 8
FIRST_STEPS = 16


class NoChip(RuntimeError):
    pass


def process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class Run:
    """What a metric reader reads (``metrics/<name>.py``)."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)


def warm_up(engine, vocab: int, seed: int) -> None:
    """Every slot active, twice: admission into fresh and into freed
    slots, one decode at the cell's one shape, sampling and retire."""
    import numpy as np

    from program import request

    rng = np.random.default_rng([int(seed), 1])
    n = engine.config.batch_size
    for k in range(WARM_STEPS):
        for i in range(n):
            engine.submit(request(uid=-(k * n + i) - 1,
                                  prompt=[int(rng.integers(1, vocab))],
                                  max_new_tokens=1))
        engine.step()


def end_to_end(run: Run) -> tuple[dict, int, int]:
    """(metrics, attempted, failed) of the window from the client
    driver's timestamps."""
    from stats import percentile

    t_close = run.steps[-1].end
    measured = t_close - run.t0
    recs = run.records
    # requests submitted as the window closed never had a chance to run
    in_window = [r for r in recs if r.submitted < run.last_hook]
    tokens = sum(len(r.token_times) for r in recs)
    ttft = [(r.token_times[0] if r.token_times else t_close) - r.submitted
            for r in in_window]
    # every gap between consecutive output tokens of one request
    gaps = [b - a for r in recs
            for a, b in zip(r.token_times, r.token_times[1:])]
    if not tokens or not gaps:
        raise RuntimeError("the window holds no two output tokens of one "
                           "request; lengthen --seconds")
    metrics = {
        "out_tok_s": {"value": tokens / measured, "unit": "tokens/s"},
        "itl_p95_ms": {"value": 1e3 * percentile(gaps, 95), "unit": "ms"},
        "ttft_p95_s": {"value": percentile(ttft, 95), "unit": "s"},
    }
    return metrics, len(in_window), run.rejected


def cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, workload entry, configuration, traffic mix) of a
    cell."""
    bench = spec.benchmark()
    wl = spec.workload(bench, workload)
    return (bench, wl, spec.config(bench, wl["config"]),
            spec.traffic(wl["traffic"]))


def device(chips: int):
    """JAX's devices; raises :class:`NoChip` unless they are ``chips``
    TPU chips or more."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices


def enable_cache() -> None:
    """JAX's persistent compile cache at the program's fixed path
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
    keeping every program: the eager decode step is hundreds of small
    ones."""
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def serve(conf: dict, mix: dict, seed: int, seconds: float, trace: bool,
          dev) -> Run:
    """Set-up and the window; returns what was measured, with the
    program's state freed.  ``trace``: trace the first steps and keep
    the trace's extract (``Run.trace_events``)."""
    import jax

    import loadgen
    import program
    import weights
    from window import CompileCounter, GcPauses, StepLog

    params = weights.make_params(conf, seed)
    jax.block_until_ready(params)
    t = time.perf_counter()
    engine = program.build(conf, params)
    jax.block_until_ready(jax.tree.leaves(engine.adapter.tree))
    pack_s = time.perf_counter() - t
    warm_up(engine, conf["vocab_size"], seed)
    adapter, config = engine.adapter, engine.config
    engine.state = None
    del engine
    gc.collect()

    eng = program.fresh_engine(adapter, config)
    loop = loadgen.ClosedLoop(
        eng, loadgen.Requests(mix, seed, conf["vocab_size"]), program.request)
    log = StepLog(eng)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    n_traced = 0
    try:
        with CompileCounter() as compiles, GcPauses() as gc_pauses:
            if trace:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0     # host: TraceMe spans only
                jax.profiler.start_trace(trace_dir, profiler_options=options)
            setup_s = process_age()
            t0 = time.perf_counter()
            loop.start()
            tracing = trace
            while True:
                st = log.step()
                if tracing and len(log.steps) >= TRACE_STEPS \
                        and st.end - t0 >= TRACE_SECONDS:
                    jax.profiler.stop_trace()
                    tracing, n_traced = False, len(log.steps)
                if st.end - t0 >= seconds:
                    break
            if tracing:
                jax.profiler.stop_trace()
                n_traced = len(log.steps)
        stats = dev.memory_stats() or {}
        log.remove()
        eng.state = None
        program.close(adapter)
        trace_events = None
        if trace:
            import tracing as tr

            trace_events = tr.extract(tr.xplane_file(trace_dir))
        return Run(conf=conf, mix=mix, seed=seed, params=params,
                   steps=log.steps, traced_steps=log.steps[:n_traced],
                   records=loop.records, last_hook=loop.last_hook,
                   rejected=loop.rejected, t0=t0, setup_s=setup_s,
                   pack_s=pack_s, compiles=compiles.count,
                   gc_pauses=gc_pauses.pauses,
                   memory_peak=int(stats.get("peak_bytes_in_use", 0)),
                   trace_events=trace_events,
                   trace=None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def run_cell(workload: str, seed: int, seconds: float,
             trace: bool) -> dict:
    """One run of ``workload``; returns the result line's object."""
    bench, wl, conf, mix = cell(workload)
    devices = device(wl["chips"])
    dev = devices[0]
    peaks = spec.peaks(dev.device_kind)
    enable_cache()
    run = serve(conf, mix, seed, seconds, trace, dev)
    gc.collect()                    # the engine and its adapter hold cycles
    run.peaks = peaks
    metrics, attempted, failed = end_to_end(run)
    reported = {m["name"] for m in spec.end_to_end(bench, workload)}
    metrics = {k: v for k, v in metrics.items() if k in reported}

    import check

    numbers = check.verify(conf, mix, run.params, run.records, seed)
    correct, checks = check.judge(numbers, check.limits(workload))
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": run.memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        import tracing as tr

        kernels = {m["name"][:-len("_roofline")]:
                   spec.work(m["name"][:-len("_roofline")]).TRACE_NAME
                   for m in spec.per_layer(bench, workload)
                   if m["name"].endswith("_roofline")}
        run.trace = tr.reduce(run.trace_events, kernels)
        per_layer = {}
        for m in spec.per_layer(bench, workload):
            value = spec.metric(m["name"]).read(run)
            if value is not None:
                per_layer[m["name"]] = {"value": value, "unit": m["unit"]}
        info["busy_s"] = run.trace["busy_s"]
        info["window_s"] = run.trace["window_s"]
        result["metrics"] = per_layer
        result["breakdown"] = {
            "device_ops": tr.top(run.trace["ops"]),
            "idle_gaps": tr.top(run.trace["idle_by_span"])}
    else:
        metrics["setup_s"] = {"value": run.setup_s, "unit": "s"}
        result["metrics"] = metrics
    result["device"] = info
    result["window"] = window_summary(run)
    result["checks"] = checks
    return result


def window_summary(run: Run) -> dict:
    """How the window's steps went, in fixed size whatever their number:
    the host times' median, p90, p99 and maximum; the slowest step and
    its decode call; ``slow_steps``, the steps longer than twice the
    median; the ``SLOWEST`` slowest steps as ``[index, step_ms,
    decode_ms]``, slowest first; the first ``FIRST_STEPS`` step times
    (the prefill ramp); and the compilations and garbage-collection
    pauses inside the window."""
    import heapq
    import statistics

    from stats import percentile

    ms = [1e3 * (s.end - s.start) for s in run.steps]
    median = statistics.median(ms)
    slowest = heapq.nlargest(SLOWEST, range(len(ms)), key=ms.__getitem__)
    return {"steps": len(ms), "step_ms_median": median,
            "step_ms_p90": percentile(ms, 90),
            "step_ms_p99": percentile(ms, 99),
            "step_ms_max": ms[slowest[0]], "slowest_step": slowest[0],
            "decode_ms_of_slowest": 1e3 * run.steps[slowest[0]].decode_s,
            "slow_steps": sum(m > 2 * median for m in ms),
            "slowest_steps": [[i, ms[i], 1e3 * run.steps[i].decode_s]
                              for i in slowest],
            "first_step_ms": ms[:FIRST_STEPS],
            "compiles": run.compiles,
            "gc_pauses": len(run.gc_pauses),
            "gc_ms_max": 1e3 * max((d for _, d in run.gc_pauses), default=0.0),
            "gc_ms_total": 1e3 * sum(d for _, d in run.gc_pauses)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print(f"window: {json.dumps(result['window'], allow_nan=False)}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False))  # a NaN raises: no line
    return 0


if __name__ == "__main__":
    sys.exit(main())
