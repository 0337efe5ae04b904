"""Random weights for a configuration, made on the device in one jitted
call from the seed, in the types the program is handed them (bf16
matrices and embedding, f32 norm scales).

The tree has the program's parameter layout (``repro.models.Model``):
``embed``, ``final_norm`` and one stacked ``blocks[0]`` over the
layers.  The benchmark makes it, so the program and the reference are
both given the same weights and neither makes them.
"""
from __future__ import annotations

import functools

import numpy as np


def weight_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits)."""
    import jax

    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(state[0])),
                              int(state[1]))


def shapes(conf: dict) -> dict[str, tuple[int, int]]:
    """The seven per-layer matrices, ``(K, N)`` with K the contraction."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    return {"wq": (d, h * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
            "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def make_params(conf: dict, seed: int):
    return _make(_static(conf))(weight_key(seed))


def _static(conf: dict) -> tuple:
    return (conf["num_hidden_layers"], conf["hidden_size"],
            conf["vocab_size"], tuple(sorted(shapes(conf).items())))


@functools.lru_cache(maxsize=None)
def _make(static: tuple):
    import jax
    import jax.numpy as jnp

    n_layers, d, vocab, mats = static

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(mats) + 4)

        def normal(k, shape, std):
            return (jax.random.normal(k, shape, jnp.float32)
                    * std).astype(jnp.bfloat16)

        def norm_scale(k, shape):
            return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)

        attn, mlp = {}, {}
        for i, (name, (kk, nn)) in enumerate(mats):
            w = normal(keys[i], (n_layers, kk, nn), kk ** -0.5)
            (attn if name in ("wq", "wk", "wv", "wo") else mlp)[name] = w
        block = {"norm1": {"scale": norm_scale(keys[-4], (n_layers, d))},
                 "norm2": {"scale": norm_scale(keys[-3], (n_layers, d))},
                 "attn": attn, "mlp": mlp}
        return {"embed": normal(keys[-2], (vocab, d), d ** -0.5),
                "blocks": [block],
                "final_norm": {"scale": norm_scale(keys[-1], (d,))}}

    return make
