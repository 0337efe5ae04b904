"""The control, put in the program's place, is not correct.

The control is the configuration's reference one precision lower
(``low=True``: float8_e4m3fn where the configuration states bfloat16).
At each position of seeded prompts and continuations, the gap of the
token the control puts first is read against the reference, as
``check.control_gap`` does on the chip, and the widest gap must exceed
the cell's limit.  The whole configuration (at fewer layers the input
token's own tied embedding outweighs every other logit, and nothing is
near a tie); four sequences of 128 tokens, so that a CPU test run holds
it."""
import numpy as np
import pytest

import check
import spec
import weights

CELLS = spec.benchmark()["workloads"]


@pytest.mark.parametrize("wl", CELLS, ids=lambda w: w["name"])
def test_control_exceeds_the_limit(wl):
    conf = spec.config(spec.benchmark(), wl["config"])
    params = weights.make_params(conf, 2 ** 35 + 1)
    ref = spec.reference(conf["reference"])
    w = ref.prepare(conf, params)
    rng = np.random.default_rng(5)
    tokens = rng.integers(1, conf["vocab_size"], (check.BLOCK, 128),
                          dtype=np.int32)
    mask = np.ones(tokens.shape, bool)
    control = check.widest_gap(ref, conf, w, tokens,
                               ref.control_top(conf, w, tokens), mask)
    limit = check.limits(wl["name"])["max_logit_gap"]["limit"]
    assert control > limit, (control, limit)
