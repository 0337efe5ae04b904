"""Property tests: compiled pack/unpack and the fused decode kernel are
bit-identical to the per-slot legacy paths on randomized problems
(§4-style, non-power-of-two, lane-capped, multi-interval).

Skipped gracefully where hypothesis is not installed (the deterministic
equivalence suite in test_exec_plan.py always runs).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
from hypothesis import given, settings

from conftest import problems
from repro.core.baselines import homogeneous_layout
from repro.core.codegen import pack_arrays, random_codes, unpack_arrays
from repro.core.exec_plan import pack_compiled, unpack_compiled
from repro.core.iris import schedule


@given(problems(), st.sampled_from(["iris", "homogeneous"]), st.integers(0, 9))
@settings(max_examples=60, deadline=None)
def test_pack_unpack_bit_identical(problem, strategy, seed):
    lay = schedule(problem) if strategy == "iris" \
        else homogeneous_layout(problem)
    lay.validate()
    codes = random_codes(problem, seed=seed)
    legacy = pack_arrays(lay, codes)
    compiled = pack_compiled(lay, codes)
    assert np.array_equal(legacy, compiled)
    got = unpack_compiled(lay, compiled)
    ref = unpack_arrays(lay, legacy)
    for name, want in codes.items():
        assert np.array_equal(got[name], want)
        assert np.array_equal(ref[name], want)


@given(problems(), st.integers(0, 9))
@settings(max_examples=15, deadline=None)
def test_fused_decode_matches_per_slot(problem, seed):
    from repro.kernels.ops import decode_layout

    lay = schedule(problem)
    codes = random_codes(problem, seed=seed)
    buf = pack_compiled(lay, codes)
    fused = decode_layout(lay, buf, fused=True)
    legacy = decode_layout(lay, buf, fused=False)
    for name, want in codes.items():
        assert np.array_equal(
            np.asarray(fused[name]).astype(np.uint64), want)
        assert np.array_equal(
            np.asarray(legacy[name]).astype(np.uint64), want)
