"""Plain reference of the served model: a Llama-style block as the
program defines it, written in ``jax.numpy`` from the configuration file
alone.  It imports nothing of the program and is handed only the weights
the benchmark made and the token ids.

* Weights: every per-layer matrix quantized as the configuration states
  (symmetric, ``group_size`` rows of the contraction per scale, scale =
  amax / qmax computed in float32 and stored as bfloat16) and
  dequantized; embedding and norms as made.
* Block: token embedding times sqrt(hidden_size) (as the program's model
  does; the published block does not scale), RMS norm, rotary embedding
  (half split), grouped-query attention over every earlier position and
  itself, SiLU-gated MLP, tied output head.
* Precision: every product and sum in float32 under
  ``default_matmul_precision("highest")``; activations are stored in the
  configuration's ``torch_dtype`` where a model of that type stores
  them: the residual stream, each norm's output, the query, each
  attention output and each projection's output before it is added to
  the residual.  K/V are stored as the configuration states: in
  ``kv_dtype`` for a dense cache; for packed pages as ``kv_bits``-wide
  codes per head vector (scale amax / qmax in float32, stored bfloat16)
  of the float32 K/V, dequantized into ``torch_dtype``.  Logits stay
  float32.

``low=True`` is the control: the same computation one precision below
what the configuration states: every stored activation and the dense
K/V in float8_e4m3fn, and every activation entering a matrix
multiplication (the attention probabilities and the MLP's gated hidden
state too) rounded to it.

The whole sequence is computed at once (causal attention), which is
what decoding through a cache computes one position at a time.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _dequant(w, bits: int, group: int, dtype):
    qmax = float(2 ** (bits - 1) - 1)
    n_l, k, n = w.shape
    wg = w.astype(jnp.float32).reshape(n_l, k // group, group, n)
    amax = jnp.max(jnp.abs(wg), axis=2, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(wg / scale), -qmax, qmax)
    w = q * scale.astype(jnp.bfloat16).astype(jnp.float32)
    return w.astype(dtype).astype(jnp.float32).reshape(n_l, k, n)


def prepare(conf: dict, params) -> dict:
    """Float32 weights of the reference: the stated quantization of every
    per-layer matrix, dequantized."""
    sv = conf["serving"]
    blk = params["blocks"][0]
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    mats = {**blk["attn"], **blk["mlp"]}
    deq = jax.jit(functools.partial(_dequant, bits=sv["weight_bits"],
                                    group=sv["group_size"],
                                    dtype=jnp.dtype(conf["torch_dtype"])))
    w = {name: deq(mats[name]) for name in MATRICES}
    w["norm1"] = f32(blk["norm1"]["scale"])
    w["norm2"] = f32(blk["norm2"]["scale"])
    w["final_norm"] = f32(params["final_norm"]["scale"])
    w["embed"] = f32(params["embed"])
    return w


def _quantize_kv(x, sv: dict):
    """K/V as the cache stores them, in float32: unchanged for a dense
    cache (rounded to its type at the storage point); for packed pages,
    ``kv_bits``-wide codes per head vector times the bfloat16-stored
    scale (amax / qmax in float32)."""
    if sv["kv"] == "dense":
        return x
    qmax = float(2 ** (sv["kv_bits"] - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    codes = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    return codes * scale.astype(jnp.bfloat16).astype(jnp.float32)


def _hidden(conf: dict, w: dict, tokens, low: bool):
    """Final-norm hidden states ``(B, T, d)`` of ``tokens (B, T)``."""
    sv = conf["serving"]
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    hkv = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    eps = conf["rms_norm_eps"]
    b, t = tokens.shape
    act_dt = jnp.float8_e4m3fn if low else jnp.dtype(conf["torch_dtype"])
    kv_dt = jnp.float8_e4m3fn if low else jnp.dtype(
        sv.get("kv_dtype", conf["torch_dtype"]))

    def store(x, dt=act_dt):
        return x.astype(dt).astype(jnp.float32)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale

    inv = conf["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32)
                                 / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv     # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # (T, 1, hd/2)

    def rope(x):                                           # (B, T, n, hd)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)

    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lw):
        hn = store(rms(x, lw["norm1"]))
        q = store(rope((hn @ lw["wq"]).reshape(b, t, h, hd)))
        k = rope((hn @ lw["wk"]).reshape(b, t, hkv, hd))
        v = (hn @ lw["wv"]).reshape(b, t, hkv, hd)
        k = store(_quantize_kv(k, sv), kv_dt)
        v = store(_quantize_kv(v, sv), kv_dt)
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = store(jnp.einsum("bhqk,bkhd->bqhd", store(p), v)
                  .reshape(b, t, -1))
        x = store(x + store(o @ lw["wo"]))
        h2 = store(rms(x, lw["norm2"]))
        g = jax.nn.silu(h2 @ lw["w_gate"]) * (h2 @ lw["w_up"])
        return store(x + store(store(g) @ lw["w_down"])), None

    x = store(w["embed"][tokens] * jnp.float32(d ** 0.5))
    layers = {k: w[k] for k in (*MATRICES, "norm1", "norm2")}
    x, _ = jax.lax.scan(layer, x, layers)
    return store(rms(x, w["final_norm"]))


@functools.partial(jax.jit, static_argnames=("conf_key",))
def _top(w, tokens, conf_key):
    conf = _CONFS[conf_key]
    hid = _hidden(conf, w, tokens, low=True)
    return jnp.argmax(hid @ w["embed"].T, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("conf_key",))
def _gap(w, tokens, chosen, conf_key):
    conf = _CONFS[conf_key]
    logits = _hidden(conf, w, tokens, low=False) @ w["embed"].T
    at = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - at


#: configurations by a hashable key, for the jitted functions' statics
_CONFS: dict[str, dict] = {}


def _key(conf: dict) -> str:
    k = json.dumps(conf, sort_keys=True)
    _CONFS[k] = conf
    return k


def gap(conf: dict, w: dict, tokens: np.ndarray, chosen: np.ndarray
        ) -> np.ndarray:
    """Per position of ``tokens (B, T)``: the reference's largest logit
    minus its logit of ``chosen[b, t]``."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_gap(w, tokens, chosen, conf_key=_key(conf)))


def control_top(conf: dict, w: dict, tokens: np.ndarray) -> np.ndarray:
    """Per position of ``tokens (B, T)``: the token the control (one
    precision lower) puts first."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_top(w, tokens, conf_key=_key(conf)))
