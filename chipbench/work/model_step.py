"""The model's FLOPs in one engine step: every matmul of every row
(seven per layer and the tied output head) and attention over the
positions each row holds.  Used by ``step_mfu``."""
from __future__ import annotations

from weights import shapes


def step_flops(conf: dict, st) -> float:
    h = conf["num_attention_heads"]
    hd = conf.get("head_dim") or conf["hidden_size"] // h
    per_row = 2.0 * (conf["num_hidden_layers"]
                     * sum(k * n for k, n in shapes(conf).values())
                     + conf["hidden_size"] * conf["vocab_size"])
    attn = 4.0 * h * hd * conf["num_hidden_layers"] \
        * sum(p + 1 for p in st.positions)
    return st.rows * per_row + attn
