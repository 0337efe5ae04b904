"""End-to-end packed serving: quantize -> Iris layout -> packed decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs import get_config
from repro.core.packing import pack_bundle, layer_bundle_spec
from repro.models.model import Model
from repro.models.quantized import (
    bytes_per_token_report,
    packed_decode_step,
    quantizable,
)
from repro.quant import QuantSpec


def quantize_params(cfg, params, spec):
    """All pack/plan wiring goes through the one front door."""
    return api.pack_tree(cfg, params, spec, with_streams=False)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_config("smollm-135m").reduced(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=128, head_dim=32)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.mark.parametrize("bits", [4, 3])
def test_published_width_layer_decodes(bits):
    """One smollm-135m layer at published widths (d_model 576, d_ff
    1536, 9/3 heads, head_dim 64) decodes through ``packed_decode_step``:
    int4 on the lane-packed views, int3 stream-direct.  Guards the
    tile-legal block choice (K=576 and N=192/576 are whole-dimension
    blocks) and agrees with the float32 path over the dequantized
    weights."""
    import dataclasses

    from repro.quant.qtypes import dequantize, quantize

    cfg = dataclasses.replace(get_config("smollm-135m"), n_layers=1,
                              vocab_size=512)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(1))
    spec = QuantSpec(bits=bits, group_size=32)
    pp = api.pack_tree(cfg, params, spec)
    assert bool(pp.packed) == (bits == 4)
    state = model.init_decode_state(2, max_seq=8)
    toks = jnp.array([5, 9], jnp.int32)
    got, _ = packed_decode_step(cfg, pp, state, toks)

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    ref = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    for sub in ("attn", "mlp"):
        for name, w in params["blocks"][0][sub].items():
            ref["blocks"][0][sub][name] = jax.vmap(
                lambda wl: dequantize(quantize(wl, spec)))(w)
    m32 = Model(cfg32, remat="none")
    with jax.default_matmul_precision("highest"):
        want, _ = m32.decode_step(ref, m32.init_decode_state(2, 8), toks)
    got, want = np.asarray(got, np.float32), np.asarray(want)
    assert got.shape == (2, cfg.vocab_size) and np.isfinite(got).all()
    # bf16 embeddings, norms and attention operands on the packed path
    assert np.abs(got - want).max() <= 0.05 * np.abs(want).max()
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_compile_cache_env_wins(monkeypatch):
    """A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX: ``enable``
    reports it and configures no other directory."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, "given-cache")
    assert compile_cache.enable() == "given-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_checkout(monkeypatch, tmp_path):
    """Unset, the cache is ``<checkout>/.jax_cache``; a package imported
    from anywhere but a checkout raises instead of guessing a path."""
    from repro.launch import compile_cache

    path = compile_cache.default_dir()
    assert path.name == ".jax_cache"
    assert (path.parent / "pyproject.toml").is_file()
    monkeypatch.setattr(compile_cache, "CHECKOUT", tmp_path)
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    with pytest.raises(RuntimeError, match="not imported from a checkout"):
        compile_cache.enable()


def test_quantizable_families():
    assert quantizable(get_config("smollm-135m").reduced())
    assert quantizable(get_config("mistral-large-123b").reduced())
    assert not quantizable(get_config("rwkv6-3b").reduced())
    assert not quantizable(get_config("whisper-medium").reduced())


def test_packed_decode_matches_dense(dense_setup):
    """int8 packed decode tracks the bf16 dense path closely."""
    cfg, model, params = dense_setup
    pp = quantize_params(cfg, params, QuantSpec(bits=8, group_size=32))
    b = 2
    state = model.init_decode_state(b, max_seq=16)
    toks = jnp.array([3, 77], jnp.int32)
    dense_logits, dense_state = jax.jit(model.decode_step)(
        params, state, toks, None)
    packed_logits, packed_state = packed_decode_step(
        cfg, pp, state, toks)
    # rank agreement on the top prediction + bounded numeric gap
    d = np.asarray(dense_logits, np.float32)
    q = np.asarray(packed_logits, np.float32)
    assert np.abs(q - d).max() < 0.25 * np.abs(d).max() + 0.5
    assert (np.argmax(q, -1) == np.argmax(d, -1)).mean() >= 0.5
    assert (np.asarray(packed_state["pos"]) == 1).all()


def test_multi_step_packed_generation(dense_setup):
    cfg, model, params = dense_setup
    pp = quantize_params(cfg, params, QuantSpec(bits=8, group_size=32))
    state = model.init_decode_state(2, max_seq=16)
    toks = jnp.array([5, 9], jnp.int32)
    for i in range(4):
        logits, state = packed_decode_step(cfg, pp, state, toks)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
    assert (np.asarray(state["pos"]) == 4).all()


def test_bytes_report_orders(dense_setup):
    cfg, _, params = dense_setup
    pp4 = quantize_params(cfg, params, QuantSpec(bits=4, group_size=32))
    r = bytes_per_token_report(cfg, pp4)
    # packed < padded-int < bf16 weight traffic per decode token
    assert r["packed_MiB"] < r["bf16_MiB"]
    assert r["padded_int_MiB"] <= r["bf16_MiB"]


def test_bundle_layout_for_quantized_layer(dense_setup):
    """The Iris layout over the quantized bundle is valid and dense."""
    cfg, _, _ = dense_setup
    spec = QuantSpec(bits=3, group_size=32)
    bundle = layer_bundle_spec(cfg.d_model, cfg.d_ff, cfg.n_heads,
                               cfg.n_kv_heads, cfg.head_dim, spec)
    pb = pack_bundle(bundle, m=512)
    pb.layout.validate()
    assert pb.metrics_iris["B_eff"] > 0.95
    # dataflow due dates: attention norm precedes mlp down-projection
    comp = pb.layout.metrics().completion
    assert comp["attn_norm"] <= comp["w_down"]
