"""Order statistics and the traffic generator."""
import numpy as np
import pytest

import loadgen
import spec
from stats import percentile


@pytest.mark.parametrize("p", [0, 5, 50, 95, 100])
def test_percentile_matches_numpy(p):
    xs = list(np.random.default_rng(3).normal(size=37))
    assert percentile(xs, p) == pytest.approx(np.percentile(xs, p))


@pytest.mark.parametrize("mix", ["decode-long", "short-turns"])
def test_every_seed_offers_the_same_work(mix):
    m = spec.traffic(mix)

    def wave(seed):
        reqs = loadgen.Requests(m, seed, 49152)
        out = [reqs.next(c) for c in range(m["clients"])]
        return sorted(len(p) for p, _ in out), sorted(n for _, n in out)

    a, b = wave(1), wave(2 ** 40 + 9)
    assert a == b
    lo, hi = m["output_tokens"]["min"], m["output_tokens"]["max"]
    assert min(a[1]) >= lo and max(a[1]) <= hi
    assert min(a[0]) >= m["prompt_tokens"]["min"]


def test_seed_changes_order_and_ids():
    m = spec.traffic("decode-long")
    r1, r2 = loadgen.Requests(m, 1, 49152), loadgen.Requests(m, 2, 49152)
    a = [r1.next(c) for c in range(64)]
    b = [r2.next(c) for c in range(64)]
    assert a != b
    r3 = loadgen.Requests(m, 1, 49152)
    assert [r3.next(c) for c in range(64)] == a
