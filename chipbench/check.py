"""Whether the tokens the timed path served are correct.

Once the window has closed and the program's state is freed, a sample of
the requests served in the window is drawn from the seed: the one with
the most served tokens and up to ``SAMPLE - 1`` others, each with at
least one served token (a request still in flight at the close has
streamed its tokens already).  The configuration's plain reference runs
once over each prompt followed by its served tokens.  At every position
that produced a served token, the gap is the reference's largest logit
minus its logit of the served token: 0 where the reference agrees, small
where a near-tie fell the other way under the program's rounding.  The
number compared is the widest gap over the sample, against the cell's
limit (``limits/<cell>.json``).

Greedy decoding is the engine's default sampler, so every served token
is the program's own argmax.
"""
from __future__ import annotations

import json

import numpy as np

import spec

SAMPLE = 32
#: sequences per reference call
BLOCK = 4


def sample(records, seed: int, n: int = SAMPLE) -> list:
    served = [r for r in records if r.req.generated]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.req.generated), -r.uid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def padded_length(mix: dict) -> int:
    """One sequence length for every reference call of a mix (so its
    program compiles once): the longest prompt plus the longest output,
    rounded up to 128."""
    t = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    return -(-t // 128) * 128


def batch(recs, t_pad: int):
    """(tokens, served, mask), each ``(n, t_pad)``: prompt then served
    tokens; at the position that produced each served token, that token
    and ``True``."""
    n = -(-len(recs) // BLOCK) * BLOCK
    tokens = np.zeros((n, t_pad), np.int32)
    served = np.zeros((n, t_pad), np.int32)
    mask = np.zeros((n, t_pad), bool)
    for i, r in enumerate(recs):
        seq = list(r.req.prompt) + list(r.req.generated)
        tokens[i, :len(seq)] = seq
        p0 = len(r.req.prompt) - 1
        gen = r.req.generated
        served[i, p0:p0 + len(gen)] = gen
        mask[i, p0:p0 + len(gen)] = True
    return tokens, served, mask


def widest_gap(ref, conf: dict, w: dict, tokens, chosen, mask) -> float:
    """Largest reference gap over the masked positions, ``BLOCK``
    sequences per call."""
    worst = 0.0
    for b in range(0, len(tokens), BLOCK):
        m = mask[b:b + BLOCK]
        if not m.any():
            continue
        g = ref.gap(conf, w, tokens[b:b + BLOCK], chosen[b:b + BLOCK])
        worst = max(worst, float(g[m].max()))
    return worst


def limits(cell: str) -> dict:
    with open(spec.HERE / "limits" / f"{cell}.json") as f:
        return json.load(f)


def verify(conf: dict, mix: dict, params, records, seed: int) -> dict:
    """The numbers compared: ``{name: value}`` plus the sample's size."""
    recs = sample(records, seed)
    vocab = conf["vocab_size"]
    valid = all(0 <= t < vocab and len(r.req.generated) <= r.req.max_new_tokens
                for r in recs for t in r.req.generated)
    if not recs:
        return {"served_tokens_compared": 0, "valid_ids": valid}
    ref = spec.reference(conf["reference"])
    w = ref.prepare(conf, params)
    tokens, served, mask = batch(recs, padded_length(mix))
    return {"max_logit_gap": widest_gap(ref, conf, w, tokens, served, mask),
            "served_tokens_compared": int(mask.sum()),
            "valid_ids": valid}


def judge(numbers: dict, lim: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the compared numbers."""
    out = {}
    ok = numbers["valid_ids"] and numbers["served_tokens_compared"] > 0
    for name, spec_ in lim.items():
        limit = spec_["limit"]
        value = numbers.get(name)
        out[name] = {"value": value, "limit": limit}
        ok = ok and value is not None and limit is not None \
            and value <= limit
    out["served_tokens_compared"] = {
        "value": numbers["served_tokens_compared"], "limit": "> 0"}
    return ok, out


def control_gap(conf: dict, mix: dict, params, records, seed: int) -> float:
    """The widest gap, over the same sample and positions as
    :func:`verify`, of the token the control (the reference one
    precision lower) puts first."""
    recs = sample(records, seed)
    ref = spec.reference(conf["reference"])
    w = ref.prepare(conf, params)
    tokens, _served, mask = batch(recs, padded_length(mix))
    chosen = np.concatenate([ref.control_top(conf, w, tokens[b:b + BLOCK])
                             for b in range(0, len(tokens), BLOCK)])
    return widest_gap(ref, conf, w, tokens, chosen, mask)
