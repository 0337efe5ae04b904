"""repro.kvcache: Iris-planned packed KV-cache streams.

Covers the subsystem end to end: planning (sequence-length-independent
signature, cache-hit-on-reuse, appends never re-plan), the masked-RMW
append path against the quantize/dequantize oracle, the stream-direct
attention kernel's bit identity with the dense decode path, the numpy
host oracle, the ``kvcache`` analysis pass, and the packed-checkpoint
KV round trip gated by ``python -m repro.analysis ckpt``.
"""
import json
import pathlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kvcache import (  # noqa: E402
    PackedKVCache,
    dequantize_kv,
    kv_bundle,
    plan_kv_stack,
    quantize_kv,
)


def tiny_cfg(**kw):
    base = dict(n_layers=2, d_model=40, n_heads=4, n_kv_heads=2, d_ff=64,
                vocab_size=64)
    base.update(kw)
    return get_config("smollm-135m").reduced(**base)


def rand_kv(rng, n_slots, hkv, hd):
    k = jnp.asarray(rng.normal(size=(n_slots, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_slots, hkv, hd)), jnp.float32)
    return k, v


def fill(kvc, rng, steps, *, layers=None, slots=None):
    """Append ``steps`` tokens to every slot in ``slots`` on ``layers``."""
    man = kvc.manifest
    slots = np.arange(man.n_slots) if slots is None else np.asarray(slots)
    layers = range(man.n_layers) if layers is None else layers
    sl = jnp.asarray(slots, jnp.int32)
    for t in range(steps):
        pos = jnp.full((len(slots),), t, jnp.int32)
        for layer in layers:
            k, v = rand_kv(rng, len(slots), man.n_kv_heads, man.head_dim)
            kvc = kvc.append(k, v, pos, sl, layer=layer)
    return kvc


# ----------------------------------------------------------------------
# planning: paged growth model
# ----------------------------------------------------------------------
def test_kv_bundle_validates():
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match="bits"):
        kv_bundle(cfg, 1, 8)
    with pytest.raises(ValueError, match="page_tokens"):
        kv_bundle(cfg, 4, 0)
    names = [b.name for b in kv_bundle(cfg, 4, 8)]
    assert names == ["kv/k", "kv/k_scales", "kv/v", "kv/v_scales"]


def test_signature_is_sequence_length_independent():
    """The scheduling instance depends on the page, not the sequence:
    caches sized for different max_seq share one layout signature."""
    cfg = tiny_cfg()
    a = PackedKVCache.create(cfg, bits=3, page_tokens=4, n_slots=1,
                             max_seq=8)
    b = PackedKVCache.create(cfg, bits=3, page_tokens=4, n_slots=5,
                             max_seq=64)
    assert a.manifest.signature == b.manifest.signature
    assert a.n_pages == 2 and b.n_pages == 16


def test_create_hits_layout_cache_on_reuse():
    from repro.core.iris import LayoutCache

    cfg = tiny_cfg()
    lc = LayoutCache()
    a = PackedKVCache.create(cfg, bits=4, page_tokens=4, n_slots=2,
                             max_seq=8, cache=lc)
    assert a.plan_stats == {"scheduler_runs": 1, "cache_hits": 1}
    b = PackedKVCache.create(cfg, bits=4, page_tokens=4, n_slots=3,
                             max_seq=32, cache=lc)
    assert b.plan_stats["scheduler_runs"] == 0
    assert b.plan_stats["cache_hits"] == 2


def test_appends_never_replan():
    """The acceptance gate: growing the cache by appending tokens must
    not touch the scheduler — the planner miss counter stays frozen."""
    from repro.core.iris import LayoutCache

    cfg = tiny_cfg()
    lc = LayoutCache()
    stack = plan_kv_stack(cfg, bits=3, page_tokens=4, cache=lc)
    assert stack.scheduler_runs == 1
    kvc = PackedKVCache.create(cfg, bits=3, page_tokens=4, n_slots=2,
                               max_seq=16, cache=lc)
    misses0, hits0 = lc.misses, lc.hits
    kvc = fill(kvc, np.random.default_rng(0), 9)      # crosses 3 pages
    kvc.dense_kv(0)
    kvc.stream_tables()
    assert lc.misses == misses0, "an append re-planned the layout"
    assert lc.hits == hits0


# ----------------------------------------------------------------------
# append path vs the quantize/dequantize oracle
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bits,hd", [(3, 5), (4, 6), (8, 4)])
def test_append_bit_exact_vs_quant_oracle(bits, hd):
    """Round-tripping through packed pages reproduces exactly the
    quantize -> dequantize values (non-power-of-two head dims too)."""
    cfg = tiny_cfg(n_heads=4, n_kv_heads=2, head_dim=hd,
                   d_model=4 * hd)
    rng = np.random.default_rng(bits)
    kvc = PackedKVCache.create(cfg, bits=bits, page_tokens=4, n_slots=3,
                               max_seq=12)
    want_k = np.zeros((3, 12, 2, hd), np.float32)
    want_v = np.zeros((3, 12, 2, hd), np.float32)
    for t in range(7):
        k, v = rand_kv(rng, 3, 2, hd)
        pos = jnp.full((3,), t, jnp.int32)
        kvc = kvc.append(k, v, pos, jnp.arange(3), layer=1)
        want_k[:, t] = np.asarray(dequantize_kv(*quantize_kv(k, bits),
                                                bits))
        want_v[:, t] = np.asarray(dequantize_kv(*quantize_kv(v, bits),
                                                bits))
    kf, vf = kvc.dense_kv(1)
    assert (np.asarray(kf)[:, :7] == want_k[:, :7]).all()
    assert (np.asarray(vf)[:, :7] == want_v[:, :7]).all()
    # untouched layer stays zero pages
    assert not np.asarray(kvc.pages)[0].any()


def test_ragged_append_and_reset():
    """Interleaved ragged appends land in the right slots; reset/evict
    zero exactly the chosen slot's pages."""
    cfg = tiny_cfg()
    hd = cfg.head_dim
    rng = np.random.default_rng(7)
    kvc = PackedKVCache.create(cfg, bits=4, page_tokens=4, n_slots=3,
                               max_seq=8)
    # slot 1 gets tokens 0..2, slots 0/2 get token 0 only
    k, v = rand_kv(rng, 3, 2, hd)
    kvc = kvc.append(k, v, jnp.zeros(3, jnp.int32), jnp.arange(3), layer=0)
    for t in (1, 2):
        k1, v1 = rand_kv(rng, 1, 2, hd)
        kvc = kvc.append(k1, v1, jnp.asarray([t]), jnp.asarray([1]),
                         layer=0)
    kf, _ = kvc.dense_kv(0)
    assert np.asarray(kf)[1, 2].any() and not np.asarray(kf)[0, 2].any()
    pages_before = np.asarray(kvc.pages).copy()
    kvc2 = kvc.reset(1)
    p2 = np.asarray(kvc2.pages)
    assert not p2[:, 1].any()
    assert (p2[:, [0, 2]] == pages_before[:, [0, 2]]).all()
    kvc3 = kvc.evict(jnp.asarray([0, 2]))
    p3 = np.asarray(kvc3.pages)
    assert not p3[:, 0].any() and not p3[:, 2].any()
    assert (p3[:, 1] == pages_before[:, 1]).all()


def test_append_is_idempotent_overwrite():
    """Re-appending at an occupied position is a clean overwrite (the
    masked RMW leaves no residue of the old token)."""
    cfg = tiny_cfg()
    rng = np.random.default_rng(11)
    kvc = PackedKVCache.create(cfg, bits=3, page_tokens=4, n_slots=1,
                               max_seq=4)
    k0, v0 = rand_kv(rng, 1, 2, cfg.head_dim)
    k1, v1 = rand_kv(rng, 1, 2, cfg.head_dim)
    a = kvc.append(k1, v1, jnp.asarray([0]), jnp.asarray([0]), layer=0)
    b = kvc.append(k0, v0, jnp.asarray([0]), jnp.asarray([0]), layer=0)
    b = b.append(k1, v1, jnp.asarray([0]), jnp.asarray([0]), layer=0)
    assert (np.asarray(a.pages) == np.asarray(b.pages)).all()


# ----------------------------------------------------------------------
# stream attention: bit identity with the dense decode path
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bits,heads,hd", [(3, (4, 2), 6), (4, (4, 4), 5),
                                           (8, (6, 2), 4)])
def test_stream_attention_bit_identical_to_dense(bits, heads, hd):
    from repro.models.attention import decode_attention
    from repro.kvcache.kernels import stream_attention_cache

    h, hkv = heads
    cfg = tiny_cfg(n_heads=h, n_kv_heads=hkv, head_dim=hd, d_model=h * hd)
    rng = np.random.default_rng(bits + hd)
    kvc = PackedKVCache.create(cfg, bits=bits, page_tokens=4, n_slots=3,
                               max_seq=12)
    kvc = fill(kvc, rng, 6, layers=[0])
    pos = jnp.asarray([5, 2, 0])                 # ragged clocks
    slots = jnp.arange(3)
    q = jnp.asarray(rng.normal(size=(3, 1, h, hd)), jnp.bfloat16)
    got = stream_attention_cache(kvc, q, pos, slots, layer=0)
    want = decode_attention(q, *kvc.dense_kv(0, slots), pos)
    assert got.dtype == want.dtype
    assert (np.asarray(got).view(np.uint16) ==
            np.asarray(want).view(np.uint16)).all()


def test_stream_attention_ref_oracle():
    """The numpy host oracle: extraction/dequant is *bit* exact against
    dense_kv; the full attention output is allclose."""
    from repro.kernels.ref import stream_attention_ref, stream_kv_ref
    from repro.kvcache.kernels import stream_attention_cache

    cfg = tiny_cfg()
    hd = cfg.head_dim
    rng = np.random.default_rng(21)
    kvc = PackedKVCache.create(cfg, bits=4, page_tokens=4, n_slots=2,
                               max_seq=8)
    kvc = fill(kvc, rng, 5, layers=[0])
    slots = jnp.arange(2)
    tabs = kvc.stream_tables()
    words = np.asarray(kvc.slot_words(0, slots))
    kf, vf = kvc.dense_kv(0, slots)
    for i in range(2):
        kr, vr = stream_kv_ref(words[i], tabs, bits=4)
        assert (kr == np.asarray(kf)[i]).all()
        assert (vr == np.asarray(vf)[i]).all()
    pos = jnp.asarray([4, 4])
    q = jnp.asarray(rng.normal(size=(2, 1, cfg.n_heads, hd)), jnp.bfloat16)
    got = np.asarray(stream_attention_cache(kvc, q, pos, slots, layer=0),
                     np.float32)
    ref = stream_attention_ref(words, np.asarray(q, np.float32),
                               np.asarray(pos), tabs, bits=4)
    assert np.allclose(got, ref, atol=2e-2)


def test_packed_decode_step_stream_vs_dense_oracle():
    """Model-level gate: kv='packed' with the stream kernel produces
    logits bit-identical to the dense-oracle attention over the same
    packed pages, and ragged slot batches match the full batch."""
    from repro import api
    from repro.models.model import Model
    from repro.models.quantized import packed_decode_step
    from repro.quant import QuantSpec

    cfg = get_config("smollm-135m").reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=128)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    tree = api.pack_tree(cfg, params, QuantSpec(bits=4, group_size=32),
                         m=512)

    def run(kv_attention):
        state = model.init_decode_state(2, 16)
        state["packed_kv"] = PackedKVCache.create(
            cfg, bits=4, page_tokens=4, n_slots=2, max_seq=16)
        outs = []
        for tok in ([5, 9], [7, 3]):
            logits, state = packed_decode_step(
                cfg, tree, state, jnp.asarray(tok, jnp.int32),
                kv="packed", kv_attention=kv_attention)
            outs.append(np.asarray(logits))
        return outs, state

    a, st_a = run("stream")
    b, _ = run("dense")
    for x, y in zip(a, b):
        assert (x == y).all()
    assert np.asarray(st_a["pos"]).tolist() == [2, 2]
    # ragged: stepping only slot 1 matches the full-batch row
    state = model.init_decode_state(2, 16)
    state["packed_kv"] = PackedKVCache.create(
        cfg, bits=4, page_tokens=4, n_slots=2, max_seq=16)
    full, _ = packed_decode_step(cfg, tree, state,
                                 jnp.asarray([5, 9], jnp.int32), kv="packed")
    ragged, st = packed_decode_step(cfg, tree, state,
                                    jnp.asarray([9], jnp.int32), kv="packed",
                                    slot_ids=jnp.asarray([1], jnp.int32))
    assert (np.asarray(full)[[1]] == np.asarray(ragged)).all()
    assert np.asarray(st["pos"]).tolist() == [0, 1]


def test_packed_decode_step_requires_kv_state():
    from repro.models.quantized import packed_decode_step

    with pytest.raises(ValueError, match="kv"):
        packed_decode_step(None, None, {}, None, kv="nonsense")


# ----------------------------------------------------------------------
# pytree / jit compatibility
# ----------------------------------------------------------------------
def test_kvcache_is_a_pytree():
    cfg = tiny_cfg()
    rng = np.random.default_rng(5)
    kvc = fill(PackedKVCache.create(cfg, bits=4, page_tokens=4, n_slots=2,
                                    max_seq=8), rng, 3)
    leaves, treedef = jax.tree_util.tree_flatten(kvc)
    assert len(leaves) == 1 and leaves[0] is kvc.pages
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.manifest == kvc.manifest
    assert back.provenance == "pytree"

    @jax.jit
    def through(c):
        return c

    out = through(kvc)
    assert (np.asarray(out.pages) == np.asarray(kvc.pages)).all()
    placed = jax.device_put(kvc)
    assert (np.asarray(placed.pages) == np.asarray(kvc.pages)).all()


# ----------------------------------------------------------------------
# analysis + checkpoint gates
# ----------------------------------------------------------------------
def test_verify_kvcache_healthy_and_corrupted():
    from repro.analysis import stream_sha256
    from repro.analysis.passes import AnalysisContext, _expected_write_mask

    cfg = tiny_cfg()
    rng = np.random.default_rng(2)
    kvc = fill(PackedKVCache.create(cfg, bits=3, page_tokens=4, n_slots=2,
                                    max_seq=8), rng, 5, layers=[0])
    digest = stream_sha256(kvc.host_pages())
    rep = kvc.verify(pages_digest=digest)
    assert rep.ok, rep.render()
    assert "kvcache" in rep.passes
    # payload bit flip -> digest catches it
    bad = kvc._replace_pages(kvc.pages.at[0, 0, 0, 0, 0].set(
        kvc.pages[0, 0, 0, 0, 0] ^ jnp.uint32(1 << 3)))
    r = bad.verify(pages_digest=digest)
    assert [f.rule_id for f in r.errors] == ["kvcache/pages-digest"]
    # a bit outside the payload mask -> stray-bits catches it (the
    # masked append path can never produce one)
    exp = _expected_write_mask(AnalysisContext(program=kvc.program()),
                               kvc.manifest.logical())
    zr, zq = np.argwhere(exp != np.uint32(0xFFFFFFFF))[-1]
    free = int(np.flatnonzero(
        ~((exp[zr, zq] >> np.arange(32)) & 1).astype(bool))[0])
    bad2 = kvc._replace_pages(kvc.pages.at[0, 0, 0, zr, zq].set(
        kvc.pages[0, 0, 0, zr, zq] | jnp.uint32(1 << free)))
    assert any(f.rule_id == "kvcache/stray-bits"
               for f in bad2.verify().errors)


def test_checkpoint_kv_round_trip(tmp_path):
    from repro import api
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.models.model import Model
    from repro.quant import QuantSpec

    cfg = get_config("smollm-135m").reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=128)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    tree = api.pack_tree(cfg, params, QuantSpec(bits=4, group_size=32),
                         m=512)
    rng = np.random.default_rng(9)
    kvc = fill(PackedKVCache.create(cfg, bits=4, page_tokens=4, n_slots=2,
                                    max_seq=16), rng, 5)
    mgr = CheckpointManager(tmp_path)
    mgr.save_packed(7, tree, kv=kvc)
    rep = mgr.verify_packed(7)
    assert rep.ok, rep.render()
    assert "kvcache" in rep.passes
    kvc2 = mgr.restore_kv(7)
    assert kvc2.provenance == "checkpoint"
    assert (np.asarray(kvc2.pages) == np.asarray(kvc.pages)).all()
    for layer in range(2):
        a, b = kvc.dense_kv(layer), kvc2.dense_kv(layer)
        assert (np.asarray(a[0]) == np.asarray(b[0])).all()
        assert (np.asarray(a[1]) == np.asarray(b[1])).all()
    # pre-KV checkpoints still load, and probe as None
    mgr.save_packed(8, tree)
    assert mgr.restore_kv(8) is None
    pt, _ = mgr.restore_packed(8)
    assert pt.manifest.arch == tree.manifest.arch


def test_analysis_cli_gates_kv_checkpoint(tmp_path, capsys):
    """``python -m repro.analysis ckpt`` must pass a clean KV snapshot
    and fail a corrupted one (exit code is the CI gate)."""
    import repro.analysis.__main__ as cli
    from repro import api
    from repro.analysis import AnalysisError
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.models.model import Model
    from repro.quant import QuantSpec

    cfg = get_config("smollm-135m").reduced(
        n_layers=1, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=128)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    tree = api.pack_tree(cfg, params, QuantSpec(bits=4, group_size=32),
                         m=512)
    rng = np.random.default_rng(13)
    kvc = fill(PackedKVCache.create(cfg, bits=4, page_tokens=4, n_slots=1,
                                    max_seq=8), rng, 3)
    mgr = CheckpointManager(tmp_path)
    d = pathlib.Path(mgr.save_packed(1, tree, kv=kvc))
    assert cli.main(["ckpt", str(tmp_path), "--step", "1"]) == 0
    # flip one page bit on disk
    man = json.loads((d / "manifest.json").read_text())
    for meta in man["leaves"]:
        arr = np.load(d / meta["file"])
        if arr.dtype == np.uint32 and arr.ndim == 5:
            arr[0, 0, 0, 0, 0] ^= np.uint32(1)
            np.save(d / meta["file"], arr)
            break
    assert cli.main(["ckpt", str(tmp_path), "--step", "1"]) == 1
    with pytest.raises(AnalysisError, match="kvcache/pages-digest"):
        mgr.restore_kv(1)
    capsys.readouterr()


# ----------------------------------------------------------------------
# deterministic random-walk subset of the property suite (always runs;
# the hypothesis version lives in test_kvcache_property.py)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bits,hd,seed", [(3, 5, 0), (4, 6, 1), (8, 4, 2)])
def test_random_walk_matches_dense_oracle(bits, hd, seed):
    from conftest import run_kv_walk

    rng = np.random.default_rng(seed)
    ops = []
    for _ in range(12):
        if rng.random() < 0.25:
            ops.append(("reset", int(rng.integers(0, 3))))
        else:
            ops.append(("append", sorted(
                set(int(x) for x in rng.integers(0, 3, size=2)))))
    run_kv_walk(bits, hd, ops, seed)
