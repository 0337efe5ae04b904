"""Pallas SSD scan kernel vs the pure-JAX recurrence oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.linear_scan import ssd_scan
from repro.models.linear_attention import recurrent_scan


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.5


@pytest.mark.parametrize("shape", [
    # (B, T, H, dk, dv, chunk)
    (1, 64, 2, 16, 16, 16),
    (2, 128, 3, 32, 32, 32),
    (2, 256, 2, 64, 64, 128),
])
def test_matches_recurrence(shape):
    b, t, h, dk, dv, chunk = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = _rand(ks[0], (b, t, h, dk)), _rand(ks[1], (b, t, h, dk))
    v = _rand(ks[2], (b, t, h, dv))
    logw = -jax.nn.softplus(_rand(ks[3], (b, t, h)))      # <= 0
    got = ssd_scan(q, k, v, logw, chunk=chunk)
    want, _ = recurrent_scan(q, k, v, logw[..., None], rwkv_mode=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_state_carries_across_chunks():
    """A distant token must influence outputs many chunks later."""
    b, t, h, d = 1, 128, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand(ks[i], (b, t, h, d)) for i in range(3))
    logw = jnp.full((b, t, h), -0.01)            # slow decay
    base = ssd_scan(q, k, v, logw, chunk=16)
    v2 = v.at[0, 3].add(10.0)                    # perturb token 3
    pert = ssd_scan(q, k, v2, logw, chunk=16)
    # tokens in later chunks see the perturbation through the carry
    assert float(jnp.abs(pert[0, 100] - base[0, 100]).max()) > 1e-3


def test_strong_decay_forgets():
    b, t, h, d = 1, 64, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (_rand(ks[i], (b, t, h, d)) for i in range(3))
    logw = jnp.full((b, t, h), -20.0)            # ~instant forgetting
    out = ssd_scan(q, k, v, logw, chunk=16)
    # each token only sees itself: o_t ~ (q_t . k_t) v_t
    expect = jnp.einsum("bthd,bthd->bth", q, k)[..., None] * v
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-3, atol=1e-3)


def test_bad_chunk_rejected():
    z = jnp.zeros((1, 100, 1, 8))
    with pytest.raises(ValueError):
        ssd_scan(z, z, z, jnp.zeros((1, 100, 1)), chunk=64)
