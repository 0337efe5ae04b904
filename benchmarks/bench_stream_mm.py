"""Stream-direct vs two-pass serving bench on the int3 LM layer bundle.

The ISSUE-6 acceptance measurement: one decode token's worth of weight
matmuls (all seven projections of a dense decoder layer) served two ways
from the *same* packed Iris stream:

* **fused** — ``kernels.stream_matmul`` per tensor: the ExecProgram slot
  tables are consulted inside the matmul prologue, weights go
  HBM -> registers -> MXU with no dense intermediate;
* **two-pass** — the legacy path the paper's thesis indicts: one fused
  Pallas layout-decode materializes every element, then each projection
  re-packs its dense codes and runs the lane-packed ``packed_matmul``
  (int3 is not lane-packable, so the dense codes ride 8-bit containers
  — the same re-bias the test-suite oracle uses, value-exact).

Bundle: ``layer_bundle_spec(576, 1536, 9, 3, 64, int3)`` (smollm-135m
geometry) on a 512-bit bus; group_size=64 — the per-column (K//g, N)
scale grid every matmul needs must tile K=576, which 128 does not.

Writes BENCH_stream_mm.json at the repo root (GB/s + rows/s per path)
and raises SystemExit(1) if the two paths are not bit-identical.

CLI:  PYTHONPATH=src python benchmarks/bench_stream_mm.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np


def _timeit_min(fn, repeats: int = 3, warmup: int = 1) -> float:
    """Best-of-N in us — robust to container scheduler noise."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def run(quick: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.exec_plan import (
        lower_exec,
        pack_compiled,
        stream_matmul_tables,
    )
    from repro.core.iris import schedule
    from repro.core.packing import (
        bundle_problem,
        layer_bundle_spec,
        pad_bundle_elements,
    )
    from repro.kernels.layout_decode import decode_layout_fused
    from repro.kernels.packed_matmul import packed_matmul
    from repro.kernels.stream_matmul import stream_matmul, stream_words
    from repro.quant import QuantSpec, pack_codes_u32, quantize

    if quick:
        d_model, d_ff, heads, kv, hd, reps = 256, 512, 4, 2, 64, 2
    else:
        d_model, d_ff, heads, kv, hd, reps = 576, 1536, 9, 3, 64, 3
    bits, g, batch = 3, 64, 8
    spec = QuantSpec(bits=bits, group_size=g)
    bundle = layer_bundle_spec(d_model, d_ff, heads, kv, hd, spec)
    shapes = {
        "wq": (d_model, heads * hd),
        "wk": (d_model, kv * hd),
        "wv": (d_model, kv * hd),
        "wo": (heads * hd, d_model),
        "w_gate": (d_model, d_ff),
        "w_up": (d_model, d_ff),
        "w_down": (d_ff, d_model),
    }

    # quantized data for every bundle tensor (weights + scales + norms)
    key = jax.random.PRNGKey(0)
    data: dict[str, np.ndarray] = {}
    for name, (k_, n_) in shapes.items():
        key, sub = jax.random.split(key)
        qt = quantize(jax.random.normal(sub, (k_, n_), jnp.float32), spec)
        data[name] = np.asarray(qt.codes).reshape(-1).astype(np.uint64)
        data[f"{name}_scales"] = np.asarray(jax.lax.bitcast_convert_type(
            qt.scales, jnp.uint16)).reshape(-1).astype(np.uint64)
    for b in bundle:
        if b.name not in data:                        # the norm vectors
            key, sub = jax.random.split(key)
            data[b.name] = np.asarray(jax.lax.bitcast_convert_type(
                jax.random.normal(sub, (b.n_elems,), jnp.float32)
                .astype(jnp.bfloat16), jnp.uint16)).astype(np.uint64)

    # schedule + lower + pack the unified stream once (load-time work)
    prob = bundle_problem(bundle, m=512)
    lay = schedule(prob)
    prog = lower_exec(lay, elem_widths=tuple(b.width_bits for b in bundle))
    buf = pack_compiled(lay, pad_bundle_elements(prob, prog, data),
                        program=prog)
    sw = stream_words(prog, buf)
    tabs = {name: stream_matmul_tables(lay, name, shp,
                                       scales=f"{name}_scales",
                                       group_size=g, program=prog)
            for name, shp in shapes.items()}
    xs = {}
    for name, (k_, _) in shapes.items():
        key, sub = jax.random.split(key)
        xs[name] = jax.random.normal(sub, (batch, k_), jnp.float32)

    def _bk(k_):
        # largest K block <= 512 that tiles K in whole groups — the SAME
        # split must go to both kernels so the accumulation order (and
        # hence bit-identity) matches
        return max(x for x in range(g, min(512, k_) + 1, g) if k_ % x == 0)

    def _bn(n_):
        return max(x for x in range(1, min(128, n_) + 1) if n_ % x == 0)

    def fused_token():
        outs = {}
        for name, (k_, n_) in shapes.items():
            t = tabs[name]
            outs[name] = stream_matmul(
                xs[name], sw, t, block_k=_bk(k_), block_n=_bn(n_))
        jax.block_until_ready(list(outs.values()))
        return outs

    rebias = 128 - (1 << (bits - 1))

    def two_pass_token():
        dec = decode_layout_fused(lay, buf, program=prog)
        outs = {}
        for name, (k_, n_) in shapes.items():
            codes = (jnp.asarray(dec[name])[:k_ * n_].reshape(k_, n_)
                     .astype(jnp.uint8) + rebias)
            scales = jax.lax.bitcast_convert_type(
                jnp.asarray(dec[f"{name}_scales"])[:(k_ // g) * n_]
                .astype(jnp.uint16).reshape(k_ // g, n_), jnp.bfloat16)
            pw = pack_codes_u32(codes, 8)
            outs[name] = packed_matmul(
                xs[name], pw, scales, bits=8, group_size=g,
                block_k=_bk(k_), block_n=_bn(n_))
        jax.block_until_ready(list(outs.values()))
        return outs

    fused_out = fused_token()                    # trace + equivalence ref
    two_out = two_pass_token()
    identical = all(
        np.array_equal(np.asarray(fused_out[n]), np.asarray(two_out[n]))
        for n in shapes)

    fused_us = _timeit_min(fused_token, repeats=reps, warmup=0)
    two_us = _timeit_min(two_pass_token, repeats=reps, warmup=0)

    stream_bytes = int(np.asarray(buf).nbytes)
    row = ("stream_mm/fused,{:.1f},two_pass_us={:.0f};speedup={:.2f}x;"
           "GBps={:.3f};rows_per_s={:.0f};identical={}")
    print(row.format(fused_us, two_us, two_us / fused_us,
                     stream_bytes / 1e3 / fused_us,
                     lay.c_max / (fused_us / 1e6), identical), flush=True)

    out = {
        "quick": quick,
        "problem": {
            "name": "lm_layer_bundle_int3_m512",
            "bits": bits, "group_size": g, "batch": batch,
            "d_model": d_model, "d_ff": d_ff,
            "m": prob.m, "c_max": lay.c_max,
            "stream_bytes": stream_bytes,
            "matmuls_per_token": len(shapes),
        },
        "fused": {
            "us_per_token": fused_us,
            "GBps": stream_bytes / 1e3 / fused_us,
            "rows_per_s": lay.c_max / (fused_us / 1e6),
        },
        "two_pass": {
            "us_per_token": two_us,
            "GBps": stream_bytes / 1e3 / two_us,
            "rows_per_s": lay.c_max / (two_us / 1e6),
        },
        "speedup": two_us / fused_us,
        "fused_below_two_pass": bool(fused_us < two_us),
        "equivalence": {"outputs_identical": bool(identical)},
    }
    path = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_stream_mm.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    if not identical:
        raise SystemExit(
            "stream-mm bench: fused path is NOT bit-identical to two-pass")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
