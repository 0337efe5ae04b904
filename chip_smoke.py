"""Bring-up smoke: serve smollm-135m at published widths on one TPU.

    python chip_smoke.py [--seed 0]

One process, one chip, no child processes.  Exits non-zero, printing no
result, unless JAX's first device is a TPU.  Parameters come from
``--seed`` (nothing is downloaded); layouts are planned from scratch.

The engine is built by :func:`repro.launch.serve.build_engine`, the
construction the serving CLI uses.  Four requests (8-16 prompt tokens, 8
new tokens each) are served at batch 4 in each phase:

* A: int8 (the CLI default) and int4 lane-packed weights, dense KV;
* B: int3 stream-direct weights with packed int3 KV pages read by the
  stream-attention kernel.

Each phase must complete every request, and its first-step logits are
checked against a plain float32 ``jax.numpy`` reference over the
dequantized weights under ``default_matmul_precision("highest")``:
``Model.decode_step`` for A; for B a plain decode (:func:`reference_steps`)
whose K/V go through int3 quantization as the packed pages store them,
judged over the engine's first step and ``STEPS`` teacher-forced steps
of the served sequences, which cross a KV page boundary.  B is further
checked per layer: every stream attention of those steps against plain
numpy attention over the K/V handed to the cache's append, and the
stream-attention kernel alone over pages the cache's own append wrote,
at ragged positions.  Every kernel the phase ran is lowered again with the
arguments it was called with and must be a Mosaic kernel
(``tpu_custom_call``).  ``HostFallbackWarning`` is an error.  All times
printed are smoke times, not benchmarks.  The last line of standard
output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import warnings
from pathlib import Path

ARCH = "smollm-135m"
BATCH = 4
MAX_NEW = 8
#: 16 prompt + 8 new tokens fit with room; stream attention holds the
#: whole max_seq per grid step in VMEM (ceiling probed below)
MAX_SEQ = 64
N_REQUESTS = 4
#: KV page size of the packed cache (``PackedAdapter``'s default)
PAGE_TOKENS = 8
#: teacher-forced steps of phase B's stepped checks: the last four write
#: and read a second KV page
STEPS = 12
#: per-slot positions of the kernel-level attention check: full and
#: partial second pages, and one slot still on its first page
ATT_POS = (11, 8, 9, 3)

#: (name, weight bits, kv) per phase
PHASES = (("A-int8", 8, "dense"), ("A-int4", 4, "dense"),
          ("B-int3", 3, "packed"))

#: Bound on max |logit - reference| of a decode step, as a share of the
#: reference's largest |logit|.  The reference uses the same dequantized
#: weights (and, for B, the same KV rounding; see KV_TIE), so only
#: arithmetic differs: the packed path keeps bf16 embeddings, norms and
#: residuals and bf16 query/KV operands in attention (relative error
#: ~2^-8 per rounding, compounding over 30 residual layers; A's chip
#: runs: 0.021 int8, 0.025 int4).
REL_TOL = 0.05
#: Least share of rows whose argmax agrees with the reference: random
#: weights leave near-tied top logits, so one flipped row in four passes.
TOP1_MIN = 0.75
#: Bound on the attention checks, as a share of the largest |output|:
#: the reference gets the same bf16 query and K/V operands, so the
#: kernel differs by its bf16 output (2^-9 relative) and the rounding of
#: its f32 softmax and matmul passes, while one mis-masked, mis-paged or
#: mis-extracted token among <= 12 moves the output by ~1/12 of a
#: value.
ATT_TOL = 1e-2
#: Phase B's int3 KV pages make the served logits a discontinuous function
#: of K/V: where K/V/scale sits near a rounding boundary, the serving
#: path's bf16 arithmetic and the reference's f32 round it to different
#: codes, a whole step (amax/3) apart, and the flips compound over
#: layers (plain rounding: 0.06 of max |logit| at one layer, 0.59 at
#: thirty, CPU rehearsal).  So the reference rounds a K/V value the way
#: the serving path did when the served code lies within KV_TIE code
#: units of the reference's own rounding interval (|u - code| <= 0.5 +
#: KV_TIE, u the reference's unrounded K/V / scale), and by its own
#: rounding otherwise.  The served codes come from the K/V the serving
#: step hands the cache's append, rounded by this script's quantizer,
#: never from the pages.  KV_TIE sits above the largest |u_ref - u_served|
#: the correct path shows (printed) and far below the whole code step a
#: wrong K/V, append, page or mask moves a value by.
KV_TIE = 0.25
#: max_seq values tried to find stream attention's VMEM ceiling
VMEM_PROBE = (512, 768, 1024, 2048)


class _KernelLog:
    """Records each kernel launch (argument shapes + static options) so
    the smoke can lower exactly what ran and check it is Mosaic."""

    def __init__(self):
        self.calls: dict[tuple, tuple] = {}
        self._orig: list[tuple] = []

    def wrap(self, module, name: str) -> None:
        import jax

        fn = getattr(module, name)
        self._orig.append((module, name, fn))

        def recorded(*args, **kw):
            specs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                          for a in args)
            key = (name, tuple((s.shape, str(s.dtype)) for s in specs),
                   tuple(sorted((k, str(v)) for k, v in kw.items())))
            self.calls.setdefault(key, (fn, specs, kw))
            return fn(*args, **kw)

        setattr(module, name, recorded)

    def restore(self) -> None:
        for module, name, fn in self._orig:
            setattr(module, name, fn)

    def check_mosaic(self) -> list[str]:
        seen = []
        for (name, _shapes, _kw), (fn, specs, kw) in self.calls.items():
            if kw.get("interpret"):
                raise RuntimeError(f"{name} ran in interpret mode")
            text = fn.lower(*specs, **kw).as_text()
            if "tpu_custom_call" not in text:
                raise RuntimeError(f"{name} did not lower to Mosaic")
            seen.append(name)
        return seen


def _requests(rng, vocab: int):
    from repro.engine import EngineRequest

    return [EngineRequest(uid=i,
                          prompt=rng.integers(1, vocab,
                                              rng.integers(8, 17)).tolist(),
                          max_new_tokens=MAX_NEW)
            for i in range(N_REQUESTS)]


def reference_params(cfg, params, bits: int):
    """(float32 config, float32 params with every quantized matrix
    replaced by its dequantized ``bits``-wide value)."""
    import jax
    import jax.numpy as jnp

    from repro.quant import QuantSpec
    from repro.quant.qtypes import dequantize, quantize
    from repro.tree import _QUANT_NAMES

    spec = QuantSpec(bits=bits, group_size=32)
    ref = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    blk = ref["blocks"][0]
    for sub in ("attn", "mlp"):
        for name in _QUANT_NAMES:
            if name in blk[sub]:
                w = params["blocks"][0][sub][name]
                blk[sub][name] = jax.vmap(
                    lambda wl: dequantize(quantize(wl, spec)))(w)
    return dataclasses.replace(cfg, dtype="float32"), ref


def reference_logits(cfg, params, bits: int, tokens):
    """First-step float32 logits of ``Model.decode_step`` over the
    dequantized weights (the plain ``jax.numpy`` reference)."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import Model

    cfg32, ref = reference_params(cfg, params, bits)
    model = Model(cfg32, remat="none")
    state = model.init_decode_state(len(tokens), MAX_SEQ)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.decode_step)(
            ref, state, jnp.asarray(tokens, jnp.int32), None)
    return logits


def kv_unrounded(x, bits: int):
    """(x / scale, scale) of plain ``bits``-wide KV quantization of
    ``x (..., head_dim)``: one scale per head vector, amax / qmax in
    float32."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / float(2 ** (bits - 1) - 1), 1.0)
    return x / scale, scale


def kv_round_trip(x, bits: int):
    """What a ``bits``-wide KV page gives back for ``x``: codes
    round(x / scale) clipped to +-qmax, times the scale stored as bf16."""
    import jax.numpy as jnp

    qmax = float(2 ** (bits - 1) - 1)
    u, scale = kv_unrounded(x, bits)
    codes = jnp.clip(jnp.round(u), -qmax, qmax)
    return codes * scale.astype(jnp.bfloat16).astype(jnp.float32)


def reference_steps(cfg, params, bits: int, tokens, served):
    """Float32 logits ``(T, B, vocab)`` of a plain decode of ``tokens``
    ``(B, T)``, one token per step from position 0, over the dequantized
    weights, every K/V vector stored ``bits`` wide before attention reads
    it, rounded near ties as ``served`` ``(k, v)``, each ``(T, L, B,
    n_kv_heads, head_dim)``, was (``KV_TIE``); and, over all K/V
    values, (served codes taken, served codes refused, largest |u_ref -
    u_served|).  Dense archs: RMS or layer norm, RoPE, GQA, gated MLP,
    no biases."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import (
        activation,
        apply_norm,
        apply_rope,
        rope_freqs,
    )

    cfg32, ref = reference_params(cfg, params, bits)
    assert not cfg.use_bias and cfg.tie_embeddings
    b, n_steps = tokens.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    inv_freq = rope_freqs(cfg32)
    seq = jnp.arange(MAX_SEQ)
    qmax = float(2 ** (bits - 1) - 1)

    def store(x, x_served):
        u, scale = kv_unrounded(x, bits)
        u_served = kv_unrounded(x_served, bits)[0]
        c_served = jnp.round(u_served)
        take = jnp.abs(u - c_served) <= 0.5 + KV_TIE
        codes = jnp.clip(jnp.where(take, c_served, jnp.round(u)), -qmax, qmax)
        differs = c_served != jnp.round(u)
        stats = jnp.stack([jnp.sum(differs & take), jnp.sum(differs & ~take),
                           jnp.max(jnp.abs(u - u_served))])
        return codes * scale.astype(jnp.bfloat16).astype(jnp.float32), stats

    def layer(pos, x, lp):
        p, kc, vc, k_served, v_served = lp
        hn = apply_norm(cfg32, p["norm1"], x)
        q = (hn @ p["attn"]["wq"]).reshape(b, 1, h, hd)
        k = (hn @ p["attn"]["wk"]).reshape(b, 1, hkv, hd)
        v = (hn @ p["attn"]["wv"]).reshape(b, 1, hkv, hd)
        at = jnp.full((b, 1), pos)
        q = apply_rope(q, at, inv_freq, cfg.mrope_sections)[:, 0]
        k = apply_rope(k, at, inv_freq, cfg.mrope_sections)[:, 0]
        k, k_stats = store(k, k_served)
        v, v_stats = store(v[:, 0], v_served)
        kc = kc.at[:, pos].set(k)
        vc = vc.at[:, pos].set(v)
        kr = jnp.repeat(kc, h // hkv, axis=2)            # (B, S, H, hd)
        vr = jnp.repeat(vc, h // hkv, axis=2)
        s = jnp.einsum("bhd,bshd->bhs", q, kr) * hd ** -0.5
        s = jnp.where(seq <= pos, s, -jnp.inf)
        o = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s, axis=-1), vr)
        x = x + o.reshape(b, h * hd) @ p["attn"]["wo"]
        h2 = apply_norm(cfg32, p["norm2"], x)
        m = p["mlp"]
        x = x + (activation(cfg.act, h2 @ m["w_gate"])
                 * (h2 @ m["w_up"])) @ m["w_down"]
        return x, (kc, vc, k_stats, v_stats)

    def run(ref, tokens, served):
        def step(caches, inp):
            tok, pos, k_served, v_served = inp
            x = ref["embed"][tok] * cfg.d_model ** 0.5
            x, (kc, vc, *stats) = jax.lax.scan(
                lambda xx, lp: layer(pos, xx, lp), x,
                (ref["blocks"][0], *caches, k_served, v_served))
            x = apply_norm(cfg32, ref["final_norm"], x)
            return (kc, vc), (x @ ref["embed"].T, jnp.stack(stats))

        zeros = jnp.zeros((cfg.n_layers, b, MAX_SEQ, hkv, hd), jnp.float32)
        logits, stats = jax.lax.scan(
            step, (zeros, zeros), (tokens.T, jnp.arange(n_steps), *served))[1]
        stats = stats.reshape(-1, 3)
        return logits, (stats[:, 0].sum(), stats[:, 1].sum(),
                        stats[:, 2].max())

    with jax.default_matmul_precision("highest"):
        logits, (taken, refused, dev) = jax.jit(run)(
            ref, jnp.asarray(tokens, jnp.int32),
            tuple(jnp.asarray(a, jnp.float32) for a in served))
    return logits, (int(taken), int(refused), float(dev))


def _compare(got, want) -> tuple[float, float]:
    """(max |got - want| over max |want|, argmax agreement)."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
    return rel, float((got.argmax(-1) == want.argmax(-1)).mean())


def stepped_logits(cfg, tree, seqs, kv_bits: int):
    """Logits ``(T, B, vocab)`` of serving with ``kv_bits``-wide packed
    KV pages and stream attention, teacher-forced through
    ``PackedAdapter.step`` (the engine's step) from position 0."""
    import numpy as np

    from repro.engine import PackedAdapter

    adapter = PackedAdapter(cfg, tree, kv="packed", kv_bits=kv_bits,
                            page_tokens=PAGE_TOKENS)
    state = adapter.init_state(BATCH, MAX_SEQ)
    out = []
    for t in range(seqs.shape[1]):
        logits, state = adapter.step(state, seqs[:, t], range(BATCH))
        out.append(logits)
    return np.stack(out)


class _AttentionLog:
    """Records, in call order, what the serving step hands the packed KV
    cache (raw K/V per append) and the stream attention (query and
    output) at every layer, so each attention call can be recomputed
    plainly from the values appended before it."""

    def __enter__(self):
        import repro.models.attention as attn
        from repro.kvcache import PackedKVCache

        self.events: list[tuple] = []
        self._saved = ((PackedKVCache, "append", PackedKVCache.append),
                       (attn, "stream_decode_attention",
                        attn.stream_decode_attention))
        append, attend = self._saved[0][2], self._saved[1][2]

        def logged_append(kvc, k, v, pos, slot_ids, *, layer):
            self.events.append(("append", layer, k, v, pos, slot_ids))
            return append(kvc, k, v, pos, slot_ids, layer=layer)

        def logged_attend(kvc, q, pos, slot_ids, *, layer, oracle=False):
            out = attend(kvc, q, pos, slot_ids, layer=layer, oracle=oracle)
            self.events.append(("attend", layer, q, pos, slot_ids, out))
            return out

        PackedKVCache.append = logged_append
        attn.stream_decode_attention = logged_attend
        return self

    def __exit__(self, *exc):
        for owner, name, fn in self._saved:
            setattr(owner, name, fn)

    def appended(self, n_steps: int, n_layers: int):
        """The raw K and V handed to ``append``, each ``(T, L, B,
        n_kv_heads, head_dim)``, for steps that append every slot at
        one position."""
        import numpy as np

        kv = {}
        for ev in self.events:
            if ev[0] == "append":
                _, layer, k, v, pos, slots = ev
                t = int(np.asarray(pos)[0])
                assert (np.asarray(pos) == t).all()
                assert (np.asarray(slots) == np.arange(len(slots))).all()
                kv[t, layer] = (np.asarray(k, np.float32),
                                np.asarray(v, np.float32))
        return tuple(np.stack([np.stack([kv[t, li][i]
                                         for li in range(n_layers)])
                               for t in range(n_steps)]) for i in (0, 1))

    def worst(self, bits: int) -> tuple[float, int, int]:
        """(largest max |error| over max |reference| of any attention
        call, its layer, its position): plain numpy attention over the
        :func:`kv_round_trip` of every K/V appended at positions
        0..pos of the slot, as bf16 operands like the query (the
        model's decode attention), against the kernel's output."""
        import jax.numpy as jnp
        import numpy as np

        def stored(x):
            return np.asarray(kv_round_trip(x, bits).astype(jnp.bfloat16),
                              np.float64)

        kv: dict[tuple, np.ndarray] = {}        # (layer, slot, pos)
        worst = (0.0, -1, -1)
        for ev in self.events:
            if ev[0] == "append":
                _, layer, k, v, pos, slots = ev
                kr, vr = stored(k), stored(v)
                for i, (s, p) in enumerate(zip(np.asarray(slots),
                                               np.asarray(pos))):
                    kv[layer, int(s), int(p)] = (kr[i], vr[i])
                continue
            _, layer, q, pos, slots, out = ev
            q = np.asarray(q, np.float64)[:, 0]            # (b, H, hd)
            got = np.asarray(out, np.float64)[:, 0]
            want = np.empty_like(got)
            for i, (s, p) in enumerate(zip(np.asarray(slots),
                                           np.asarray(pos))):
                hist = [kv[layer, int(s), t] for t in range(int(p) + 1)]
                g = q.shape[1] // hist[0][0].shape[0]
                kk = np.repeat(np.stack([a for a, _ in hist]), g, axis=1)
                vv = np.repeat(np.stack([b for _, b in hist]), g, axis=1)
                sc = np.einsum("hd,nhd->hn", q[i], kk) / np.sqrt(q.shape[-1])
                pr = np.exp(sc - sc.max(-1, keepdims=True))
                want[i] = np.einsum("hn,nhd->hd",
                                    pr / pr.sum(-1, keepdims=True), vv)
            rel = float(np.abs(got - want).max() / np.abs(want).max())
            if rel > worst[0]:
                worst = (rel, layer, int(np.asarray(pos).max()))
        return worst


def attention_check(cfg, rng, bits: int) -> float:
    """Stream attention over pages written by the cache's own append, at
    the ragged positions ``ATT_POS``, against plain numpy attention;
    returns max |error| over max |reference|.

    Every K/V head vector is int codes times a power of two with one
    code at +qmax, so quantization is exact and the reference reads the
    values that were appended, not the pages."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kvcache import PackedKVCache
    from repro.kvcache.kernels.stream_attention import stream_attention_cache

    b, h, hkv, hd = BATCH, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qmax = 2 ** (bits - 1) - 1
    n_tok = max(ATT_POS) + 1

    def values():
        c = rng.integers(-qmax, qmax + 1, (n_tok, b, hkv, hd))
        c[..., 0] = qmax
        return c * 2.0 ** rng.integers(-3, 3, (n_tok, b, hkv, 1))

    k, v = values(), values()
    kvc = PackedKVCache.create(cfg, bits=bits, page_tokens=PAGE_TOKENS,
                               n_slots=b, max_seq=MAX_SEQ)
    slots = jnp.arange(b, dtype=jnp.int32)
    for t in range(n_tok):
        kvc = kvc.append(jnp.asarray(k[t], jnp.float32),
                         jnp.asarray(v[t], jnp.float32),
                         jnp.full((b,), t, jnp.int32), slots, layer=0)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.bfloat16)
    got = np.asarray(stream_attention_cache(
        kvc, q, jnp.asarray(ATT_POS, jnp.int32), slots, layer=0),
        np.float32)[:, 0]
    qf = np.asarray(q, np.float64)[:, 0]
    want = np.empty_like(got)
    for i, pos in enumerate(ATT_POS):
        kk = np.repeat(k[:pos + 1, i], h // hkv, axis=1)   # (n, H, hd)
        vv = np.repeat(v[:pos + 1, i], h // hkv, axis=1)
        s = np.einsum("hd,nhd->hn", qf[i], kk) / np.sqrt(hd)
        p = np.exp(s - s.max(-1, keepdims=True))
        want[i] = np.einsum("hn,nhd->hd", p / p.sum(-1, keepdims=True), vv)
    return float(np.abs(got - want).max() / np.abs(want).max())


def run_phase(cfg, model, params, rng, bits: int, kv: str) -> dict:
    """Serve one phase through the engine; returns its checks."""
    import importlib

    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.models.quantized import packed_decode_step

    log = _KernelLog()
    for module, fn in (("repro.kernels.packed_matmul", "packed_matmul_call"),
                       ("repro.kernels.stream_matmul", "stream_matmul_call"),
                       ("repro.kvcache.kernels.stream_attention",
                        "stream_attention_call")):
        log.wrap(importlib.import_module(module), fn)
    try:
        t0 = time.perf_counter()
        engine = build_engine(cfg, model, params, packed=True, bits=bits,
                              kv=kv, batch_size=BATCH, max_seq=MAX_SEQ)
        t_build = time.perf_counter() - t0
        reqs = _requests(rng, cfg.vocab_size)
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        ctx = engine.step()                 # compiles every kernel
        first = np.asarray(ctx["logits"], np.float32)
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        engine.run_until_drained(max_steps=200)
        t_rest = time.perf_counter() - t0
        uploader = getattr(engine.adapter, "uploader", None)
        if uploader is not None:
            uploader.close()
        out = {
            "completed": engine.stats.completed, "requested": len(reqs),
            "steps": engine.stats.steps, "shape": first.shape,
            "finite": bool(np.isfinite(first).all()),
            "build_s": t_build, "first_step_s": t_first, "rest_s": t_rest,
        }
        toks = jnp.asarray([r.prompt[0] for r in reqs], jnp.int32)
        ref = reference_logits(cfg, params, bits, toks)
        if kv == "dense":
            out["end_to_end"] = _compare(first, ref)
        else:
            t0 = time.perf_counter()
            tree = engine.adapter.tree
            # weights alone: the same stream-direct tree with dense KV
            out["weights"] = _compare(packed_decode_step(
                cfg, tree, model.init_decode_state(BATCH, MAX_SEQ),
                toks)[0], ref)
            # the served sequences (prompt, then generated tokens)
            seqs = np.asarray([(r.prompt + r.generated)[:STEPS]
                               for r in reqs], np.int32)
            with _AttentionLog() as alog:
                served = stepped_logits(cfg, tree, seqs, bits)
            out["layer_attention"] = alog.worst(bits)
            ref_steps, out["ties"] = reference_steps(
                cfg, params, bits, seqs,
                alog.appended(STEPS, cfg.n_layers))
            out["first_step"] = _compare(first, ref_steps[0])
            out["end_to_end"] = _compare(served, ref_steps)
            out["steps_rel"] = [_compare(s, q)[0] for s, q in
                                zip(served, ref_steps)]
            out["attention"] = attention_check(cfg, rng, bits)
            out["checks_s"] = time.perf_counter() - t0
        out["kernels"] = sorted(set(log.check_mosaic()))
    finally:
        log.restore()
    return out


def judge(r: dict, kv: str, vocab: int) -> tuple[dict, str]:
    """Pass/fail of each check of a phase, and its report."""
    checks = {"completed": r["completed"] == r["requested"],
              "shape": r["shape"] == (BATCH, vocab),
              "finite": r["finite"]}
    if kv == "dense":
        rel, top1 = r["end_to_end"]
        checks["logits"] = rel <= REL_TOL
        checks["top1"] = top1 >= TOP1_MIN
        return checks, f"first step vs f32 reference: rel err {rel:.4g} " \
                       f"(<= {REL_TOL}) top1 {top1:.2f} (>= {TOP1_MIN})"
    wrel, wtop = r["weights"]
    checks["weights"] = wrel <= REL_TOL and wtop >= TOP1_MIN
    frel, ftop = r["first_step"]
    checks["first_step"] = frel <= REL_TOL and ftop >= TOP1_MIN
    rel, top1 = r["end_to_end"]
    checks["end_to_end"] = rel <= REL_TOL and top1 >= TOP1_MIN
    arel, alayer, apos = r["layer_attention"]
    checks["layer_attention"] = arel <= ATT_TOL
    checks["attention"] = r["attention"] <= ATT_TOL
    taken, refused, dev = r["ties"]
    detail = (
        f"weights (dense KV, first step) vs f32 reference: rel err "
        f"{wrel:.4g} (<= {REL_TOL}) top1 {wtop:.2f} (>= {TOP1_MIN}); "
        f"int3 KV vs f32 reference with int3 KV (near-tie rounding as "
        f"served, KV_TIE {KV_TIE}: {taken} codes taken, {refused} "
        f"refused, largest |u_ref - u_served| {dev:.4g}): engine's first "
        f"step rel err {frel:.4g} top1 {ftop:.2f}, {STEPS} teacher-forced "
        f"steps rel err {rel:.4g} (<= {REL_TOL}) top1 {top1:.3f} (>= "
        f"{TOP1_MIN}), per step "
        f"{','.join(f'{x:.4g}' for x in r['steps_rel'])}; stream "
        f"attention of every layer over the served int3 pages, {STEPS} "
        f"steps, vs numpy over the appended values: worst rel err "
        f"{arel:.4g} at layer {alayer} pos {apos} (<= {ATT_TOL}); at "
        f"ragged positions {ATT_POS}: rel err {r['attention']:.4g} "
        f"(<= {ATT_TOL}); smoke time: checks {r['checks_s']:.1f}s")
    return checks, detail


def probe_attention_vmem(cfg) -> tuple[int | None, str]:
    """Largest probed max_seq whose stream-attention kernel compiles for
    this chip (batch 4, int3, page 8), and the first refusal."""
    import jax
    import jax.numpy as jnp

    from repro.kvcache import PackedKVCache
    from repro.kvcache.kernels.stream_attention import stream_attention_call
    from repro.kvcache.layout import page_window_tables

    best, refusal = None, ""
    for smax in VMEM_PROBE:
        kvc = PackedKVCache.create(cfg, bits=3, page_tokens=PAGE_TOKENS,
                                   n_slots=BATCH, max_seq=smax)
        man = kvc.manifest
        tabs = page_window_tables(kvc.program(), page_tokens=PAGE_TOKENS,
                                  n_kv_heads=cfg.n_kv_heads,
                                  head_dim=cfg.head_dim, bits=3)
        n_ch = -(-man.c_max * man.words32 // 128)
        args = [jax.ShapeDtypeStruct((BATCH, n_ch, man.n_pages, 128),
                                     jnp.uint32),
                jax.ShapeDtypeStruct((BATCH, cfg.n_heads, 1, cfg.head_dim),
                                     jnp.bfloat16),
                jax.ShapeDtypeStruct((BATCH,), jnp.int32)]
        args += [jax.ShapeDtypeStruct(tabs[k].shape, jnp.uint32)
                 for k in ("k", "k_scales", "v", "v_scales")]
        try:
            stream_attention_call.lower(*args, bits=3,
                                        interpret=False).compile()
        except Exception as e:  # noqa: BLE001 - the refusal is the result
            refusal = f"max_seq={smax}: {type(e).__name__}: " \
                      f"{str(e).splitlines()[0][:160]}"
            break
        best = smax
    return best, refusal


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.layout_decode import HostFallbackWarning
    from repro.launch import compile_cache
    from repro.models.model import Model

    warnings.simplefilter("error", HostFallbackWarning)
    print(f"device: {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff "
          f"{cfg.d_ff}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
          f"{cfg.head_dim}, vocab {cfg.vocab_size}; params from seed "
          f"{args.seed} (smoke time {time.perf_counter() - t0:.1f}s)")

    failures = []
    for name, bits, kv in PHASES:
        rng = np.random.default_rng(args.seed)
        r = run_phase(cfg, model, params, rng, bits, kv)
        checks, detail = judge(r, kv, cfg.vocab_size)
        print(f"phase {name}: completed={r['completed']}/{r['requested']} "
              f"steps={r['steps']} {detail}; mosaic kernels="
              f"{','.join(r['kernels'])}; smoke time: build "
              f"{r['build_s']:.1f}s, first step (compile) "
              f"{r['first_step_s']:.1f}s, rest {r['rest_s']:.1f}s")
        bad = [k for k, ok in checks.items() if not ok]
        if bad:
            failures.append(f"{name}: {', '.join(bad)}")
    best, refusal = probe_attention_vmem(cfg)
    print(f"stream_attention VMEM ceiling (batch {BATCH}, int3, page "
          f"{PAGE_TOKENS}): max_seq {best} compiles; "
          f"{refusal or 'no refusal in probe'}; smoke max_seq={MAX_SEQ}")
    if failures:
        print("chip_smoke FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
