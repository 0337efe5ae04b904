"""Readings that a cell's correctness limit is set from.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--control] [--trace-dump <path>]

One process: for each seed, the set-up and window of a run (as
``run.py`` makes them), then the widest reference gap of the served
tokens (the number ``run.py`` compares) and, with ``--control``, the
widest gap of the tokens the control puts first at the same positions.
The control is the reference one precision below what the
configuration states (``references/<name>.py``, ``low=True``).  Prints
one JSON line per seed.  ``--trace-dump`` traces the first seed's window
and writes the trace's extract (``tracing.extract``) there.  Not run by the
benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run  # puts this directory and the checkout's src on the path

import check  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trace-dump", default=None)
    args = ap.parse_args(argv)
    bench, wl, conf, mix = run.cell(args.workload)
    try:
        devices = run.device(wl["chips"])
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    run.enable_cache()
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        trace = bool(args.trace_dump) and i == 0
        t = time.perf_counter()
        r = run.serve(conf, mix, seed, args.seconds, trace, devices[0])
        gc.collect()
        try:
            metrics, attempted, _ = run.end_to_end(r)
        except RuntimeError as e:          # too short a window: say so
            metrics, attempted = {"error": {"value": str(e)}}, None
        t_ref = time.perf_counter()
        numbers = check.verify(conf, mix, r.params, r.records, seed)
        out = {"workload": args.workload, "seed": seed,
               "steps": len(r.steps), "attempted": attempted,
               "setup_s": r.setup_s, "pack_s": r.pack_s,
               "compiles": r.compiles, "memory_peak": r.memory_peak,
               **{k: v["value"] for k, v in metrics.items()}, **numbers,
               "reference_s": time.perf_counter() - t_ref}
        if args.control:
            t_ctl = time.perf_counter()
            out["control_gap"] = check.control_gap(conf, mix, r.params,
                                                   r.records, seed)
            out["control_s"] = time.perf_counter() - t_ctl
        out["decode_ms"] = [round(1e3 * s.decode_s, 3) for s in r.steps]
        out["step_ms"] = [round(1e3 * (s.end - s.start), 3) for s in r.steps]
        out["total_s"] = time.perf_counter() - t
        if trace:
            import tracing as tr

            with open(args.trace_dump, "w") as f:
                json.dump(r.trace_events, f)
            kernels = {k: spec.work(k).TRACE_NAME for k in
                       ("packed_matmul", "stream_matmul")}
            red = tr.reduce(r.trace_events, kernels)
            out["trace"] = {k: red[k] for k in
                            ("window_s", "busy_s", "kernels", "steps")}
            out["trace"]["top_ops"] = tr.top(red["ops"], 25)
            out["trace"]["idle"] = tr.top(red["idle_by_span"], 25)
        print(json.dumps(out), flush=True)
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
