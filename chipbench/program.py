"""The system under test, as the benchmark builds and drives it.

Everything here calls the program's own front doors:
``repro.launch.serve.build_engine`` (which packs the weights through
``repro.api.pack_tree``) and ``repro.engine.Engine``.  The benchmark
hands in the weights it made and the configuration file's sizes.
"""
from __future__ import annotations

import contextlib
import sys


def model_config(conf: dict):
    """``repro.configs.base.ModelConfig`` for a Llama-style
    configuration file (Hugging Face keys)."""
    from repro.configs.base import ModelConfig

    if conf.get("model_type") != "llama":
        raise ValueError(f"{conf['name']}: model_type "
                         f"{conf.get('model_type')!r} is not served here")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf.get("head_dim") or d // h,
        act=conf["hidden_act"], norm="rmsnorm",
        use_bias=bool(conf.get("attention_bias", False)),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        max_seq_len=conf["max_position_embeddings"],
        dtype=conf["torch_dtype"])


def build(conf: dict, params):
    """The serving engine of ``conf`` over ``params``, built by
    ``build_engine``; its summary lines go to standard error."""
    from repro.launch.serve import build_engine
    from repro.models.model import Model

    sv = conf["serving"]
    cfg = model_config(conf)
    if sv["group_size"] != 32 or sv["scale_dtype"] != "bfloat16":
        raise ValueError("build_engine quantizes in groups of 32 with "
                         "bfloat16 scales")
    if sv.get("kv_bits", sv["weight_bits"]) != sv["weight_bits"]:
        raise ValueError("build_engine stores packed KV at the weight width")
    with contextlib.redirect_stdout(sys.stderr):
        return build_engine(cfg, Model(cfg, remat="none"), params,
                            packed=True, bits=sv["weight_bits"],
                            kv=sv["kv"], batch_size=sv["slots"],
                            max_seq=sv["max_seq"])


def fresh_engine(adapter, config):
    """A new engine over a warmed ``adapter`` (compiled kernels, layouts
    and uploader), with ``config`` and empty slots."""
    from repro.engine import Engine

    return Engine(adapter, config)


def request(**kw):
    from repro.engine import EngineRequest

    return EngineRequest(**kw)


def close(adapter) -> None:
    """Stop the adapter's upload thread, if it has one."""
    uploader = getattr(adapter, "uploader", None)
    if uploader is not None:
        uploader.close()
