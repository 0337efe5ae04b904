"""End-to-end driver (the paper's kind: serving/data movement): serve a
small LM with batched requests where the decode-step weights are
int-quantized, Iris-organized, and dequantized on load by the Pallas
matmul — dense bf16 weights never exist in memory.

Reports per-token weight-streaming bytes vs the bf16 and padded-int
baselines (the memory-roofline win of the paper's technique), plus the
Iris layout metrics of the per-layer stream bundles.

Run:  PYTHONPATH=src python examples/packed_serving.py [--bits 8]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import get_config
from repro.kernels.backend import interpret_mode
from repro.models.model import Model
from repro.models.quantized import bytes_per_token_report, packed_decode_step
from repro.quant import QuantSpec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config("smollm-135m").reduced(
        n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
        vocab_size=512, head_dim=64)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    spec = QuantSpec(bits=args.bits, group_size=64)

    print(f"=== Quantize + pack ({args.bits}-bit, model {cfg.name} "
          f"reduced) ===")
    # the one front door: quantize -> plan -> pack, one call, one pytree
    pp = api.pack_tree(cfg, params, spec, m=512)
    print(pp.summary())
    rep = bytes_per_token_report(cfg, pp)
    print(f"weight stream per decode token: packed={rep['packed_MiB']:.2f} "
          f"MiB  padded-int={rep['padded_int_MiB']:.2f} MiB  "
          f"bf16={rep['bf16_MiB']:.2f} MiB")
    print(f"reduction vs bf16: {rep['bf16_MiB']/rep['packed_MiB']:.2f}x")

    print("\n=== Iris stream layout per layer (repro.api façade) ===")
    stack = api.plan_layer_stack(cfg, spec, m=512)
    hom = api.compare(stack.problem, strategies=("homogeneous",))
    print(f"B_eff={stack.b_eff:.4f} "
          f"L_max={stack.plans[0].metrics.l_max} "
          f"(homogeneous: {hom['homogeneous'].l_max}); "
          f"decode units={stack.plans[0].decode_plan.n_units}; "
          f"{stack.n_layers} layers from {stack.scheduler_runs} "
          f"scheduler run(s)")

    print("\n=== Batched generation (packed decode path) ===")
    state = model.init_decode_state(args.batch, max_seq=64)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, args.batch),
                       dtype=jnp.int32)
    outs = [[] for _ in range(args.batch)]
    t0 = time.perf_counter()
    for _ in range(args.new_tokens):
        logits, state = packed_decode_step(cfg, pp, state, toks)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(args.batch):
            outs[i].append(int(toks[i]))
    dt = time.perf_counter() - t0
    for i, o in enumerate(outs):
        print(f"request {i}: {o}")
    mode = "interpret-mode" if interpret_mode() else "Mosaic"
    print(f"\n{args.batch * args.new_tokens} tokens in {dt:.1f}s "
          f"({mode} Pallas on {jax.default_backend()})")

    print("\n=== Packed checkpoint (the HBM stream is the checkpoint) ===")
    import pathlib
    import tempfile

    from repro.checkpoint.checkpoint import CheckpointManager

    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, keep_n=1)
        path = mgr.save_packed(0, pp)
        pt2, _ = mgr.restore_packed()
        same = all(
            np.array_equal(np.asarray(pp.packed[k]), np.asarray(pt2.packed[k]))
            for k in pp.packed)
        size = sum(f.stat().st_size for f in pathlib.Path(path).iterdir())
        print(f"restore bit-identical={same} layout={pt2.provenance} "
              f"on-disk={size/2**20:.2f} MiB")

    # cross-check against the dense path for the first step
    state2 = model.init_decode_state(args.batch, max_seq=64)
    t = jnp.asarray(rng.integers(0, cfg.vocab_size, args.batch), jnp.int32)
    dlog, _ = jax.jit(model.decode_step)(params, state2, t, None)
    qlog, _ = packed_decode_step(cfg, pp, state2, t)
    agree = float((np.argmax(np.asarray(dlog), -1)
                   == np.argmax(np.asarray(qlog), -1)).mean())
    print(f"top-1 agreement packed vs dense: {agree:.0%}  [OK]")


if __name__ == "__main__":
    main()
