"""Serving-engine latency/goodput bench: static vs continuous batching.

The ISSUE-7 acceptance measurement, on the int3 smollm-geometry packed
tree (stream-direct — int3 has no lane-packed kernel views):

* **bit-identity gate** — every token the continuous-batching engine
  emits must equal, bit for bit, what an *independent* single-stream
  loop (one request at a time, batch=1, straight ``packed_decode_step``
  calls) produces for the same request.  Checked for int3 and int4;
  the bench exits nonzero on any mismatch.
* **closed loop** — submit everything, drain; wall-clock tokens/s and
  step counts per admission policy.
* **open loop** — requests arrive at a swept offered load and the
  engine runs on a *virtual clock* (1 tick = 1 engine step), so the
  p50/p99-vs-load curves are deterministic and hardware-independent:
  latency is measured in decode steps, goodput in completed tokens per
  step.  Heterogeneous ``max_new_tokens`` makes the static policy pay
  for slot idling — the effect continuous batching exists to remove.

Acceptance: at equal p99 (budget = the worst p99 the static policy
posts anywhere in the sweep), continuous batching sustains strictly
higher goodput.  Written into ``BENCH_serve.json`` at the repo root.

CLI:  PYTHONPATH=src python benchmarks/bench_serve.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time


class StepClock:
    """Virtual engine clock: 1.0 per engine step, advanced by the driver."""

    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 1.0) -> None:
        self.t += dt


def _make_requests(n: int, vocab: int, seed: int):
    """Deterministic request set with heterogeneous lengths: short and
    long generations interleave, so a static batch idles slots."""
    import numpy as np

    from repro.engine import EngineRequest

    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n):
        prompt = rng.integers(1, vocab, int(rng.integers(2, 5))).tolist()
        max_new = 3 if uid % 2 == 0 else 9
        reqs.append(EngineRequest(uid=uid, prompt=prompt,
                                  max_new_tokens=max_new))
    return reqs


def _single_stream_oracle(cfg, tree, model, req):
    """Independent oracle: serve one request alone, batch=1, plain
    ``packed_decode_step`` calls — no engine, no ragged slots."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models.quantized import packed_decode_step

    state = model.init_decode_state(1, 64)
    generated: list[int] = []
    pos = 0
    while len(generated) < req.max_new_tokens and pos < 63:
        tok = req.prompt[pos] if pos < len(req.prompt) \
            else generated[-1]
        logits, state = packed_decode_step(
            cfg, tree, state, jnp.asarray([tok], jnp.int32))
        pos += 1
        if pos >= len(req.prompt):
            generated.append(int(np.asarray(logits[0]).argmax()))
    return generated


def _run_open_loop(engine, clock, arrivals, max_steps: int) -> None:
    """Feed ``(t, req)`` arrivals while stepping on the virtual clock."""
    pending = list(arrivals)
    steps = 0
    while pending or engine.has_work():
        while pending and pending[0][0] <= clock.t:
            engine.submit(pending.pop(0)[1])
        if engine.has_work():
            engine.step()
            steps += 1
            if steps >= max_steps:
                break
        clock.tick(1.0)


def run(quick: bool = False) -> dict:
    import copy

    import jax

    from repro import api
    from repro.configs import get_config
    from repro.engine import Engine, EngineConfig, PackedAdapter
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
    batch, max_seq = 4, 64

    # -- bit-identity gate: engine (continuous) vs single-stream oracle --
    n_ident = 3 if quick else 5
    identity = {}
    for bits, tree in trees.items():
        reqs = _make_requests(n_ident, cfg.vocab_size, seed=bits)
        eng = Engine(PackedAdapter(cfg, tree),
                     EngineConfig(batch_size=batch, max_seq=max_seq))
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        oracle = {r.uid: _single_stream_oracle(cfg, tree, model,
                                               copy.deepcopy(r))
                  for r in reqs}
        ok = all(r.generated == oracle[r.uid] for r in reqs)
        identity[f"int{bits}"] = {
            "requests": n_ident,
            "tokens": sum(len(r.generated) for r in reqs),
            "identical": bool(ok),
        }
        print(f"serve/bit_identity_int{bits},0.0,"
              f"tokens={identity[f'int{bits}']['tokens']};identical={ok}",
              flush=True)

    tree = trees[3]                       # the acceptance config: int3

    # -- closed loop: wall-clock throughput per policy -------------------
    n_closed = 6 if quick else 10
    closed = {}
    for policy in ("static", "continuous"):
        reqs = _make_requests(n_closed, cfg.vocab_size, seed=7)
        eng = Engine(PackedAdapter(cfg, tree),
                     EngineConfig(batch_size=batch, max_seq=max_seq,
                                  policy=policy))
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        stats = eng.run_until_drained()
        wall = time.perf_counter() - t0
        snap = eng.metrics.snapshot()
        closed[policy] = {
            "steps": stats.steps,
            "tokens": stats.tokens_generated,
            "completed": stats.completed,
            "wall_s": wall,
            "tokens_per_s": stats.tokens_generated / wall,
            "mean_batch_occupancy":
                snap["throughput"]["mean_batch_occupancy"],
        }
        print(f"serve/closed_{policy},{wall * 1e6 / stats.steps:.1f},"
              f"steps={stats.steps};tokens={stats.tokens_generated};"
              f"occupancy={closed[policy]['mean_batch_occupancy']:.2f}",
              flush=True)

    # -- open loop: p50/p99 and goodput vs offered load ------------------
    # loads in requests per engine step; capacity for batch=4 and ~9
    # steps mean service time is ~0.44 req/step continuous
    loads = (0.2, 0.45) if quick else (0.12, 0.25, 0.45)
    n_open = 8 if quick else 14
    sweep = []
    for policy in ("static", "continuous"):
        for load in loads:
            clock = StepClock()
            reqs = _make_requests(n_open, cfg.vocab_size, seed=11)
            arrivals = [(i / load, r) for i, r in enumerate(reqs)]
            eng = Engine(PackedAdapter(cfg, tree),
                         EngineConfig(batch_size=batch, max_seq=max_seq,
                                      policy=policy, max_backlog=None),
                         clock=clock)
            _run_open_loop(eng, clock, arrivals, max_steps=2000)
            snap = eng.metrics.snapshot()
            lat = snap["latency"]["total"]
            thr = snap["throughput"]
            point = {
                "policy": policy,
                "offered_load_req_per_step": load,
                "completed": snap["requests"]["completed"],
                "p50_steps": lat["p50_s"],
                "p99_steps": lat["p99_s"],
                "goodput_tokens_per_step": thr["goodput_tokens_per_s"],
                "mean_batch_occupancy": thr["mean_batch_occupancy"],
            }
            sweep.append(point)
            print(f"serve/open_{policy}_load{load},0.0,"
                  f"p50={lat['p50_s']:.1f};p99={lat['p99_s']:.1f};"
                  f"goodput={point['goodput_tokens_per_step']:.3f}",
                  flush=True)

    # -- acceptance: goodput at equal p99 --------------------------------
    static_pts = [p for p in sweep if p["policy"] == "static"]
    cont_pts = [p for p in sweep if p["policy"] == "continuous"]
    p99_budget = max(p["p99_steps"] for p in static_pts)
    static_goodput = max(p["goodput_tokens_per_step"] for p in static_pts)
    cont_under = [p["goodput_tokens_per_step"] for p in cont_pts
                  if p["p99_steps"] <= p99_budget]
    cont_goodput = max(cont_under) if cont_under else 0.0
    acceptance = {
        "p99_budget_steps": p99_budget,
        "static_goodput_tokens_per_step": static_goodput,
        "continuous_goodput_tokens_per_step": cont_goodput,
        "continuous_gt_static_at_equal_p99":
            bool(cont_goodput > static_goodput),
    }
    print(f"serve/acceptance,0.0,"
          f"static={static_goodput:.3f};continuous={cont_goodput:.3f};"
          f"p99_budget={p99_budget:.1f};"
          f"continuous_gt_static={acceptance['continuous_gt_static_at_equal_p99']}",
          flush=True)

    out = {
        "quick": quick,
        "config": {
            "arch": cfg.name, "bits": 3, "group_size": 32,
            "batch_size": batch, "max_seq": max_seq,
            "n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "weights": "stream-direct",
        },
        "bit_identity": identity,
        "closed_loop": closed,
        "open_loop_sweep": sweep,
        "acceptance": acceptance,
    }
    path = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_serve.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    if not all(v["identical"] for v in identity.values()):
        raise SystemExit(
            "serve bench: engine tokens are NOT bit-identical to the "
            "single-stream loop")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
