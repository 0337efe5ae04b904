"""Kernel work against figures worked by hand for smollm-135m."""
import pytest

import spec
from window import Step

CONF = spec.config(spec.benchmark(), "smollm-135m-w4-kv16")
CONF3 = spec.config(spec.benchmark(), "smollm-135m-w3-kv16")


def test_w_up_int4_and_int3():
    pm = spec.work("packed_matmul")
    # K = 576, N = 1536, no rows: weights and scales alone
    flops, needed = pm.matmul(0, 576, 1536, 4, 32)
    assert 576 * 1536 == 884_736
    assert needed == 442_368 + 27_648 * 2
    flops, needed = pm.matmul(0, 576, 1536, 3, 32)
    assert needed == 331_776 + 27_648 * 2
    # 64 rows: 2 M K N FLOPs, bf16 activations in and out
    flops, needed = pm.matmul(64, 576, 1536, 4, 32)
    assert flops == 2 * 64 * 576 * 1536
    assert needed == 442_368 + 55_296 + 64 * (576 + 1536) * 2


def test_layer_holds_3538944_weights():
    pm = spec.work("packed_matmul")
    calls = pm.step(CONF, Step(start=0, positions=(0,) * 64))
    assert len(calls) == 7 * 30
    per_layer = calls[:7]
    weights = sum(f for f, _ in per_layer) / (2 * 64)
    assert weights == 3_538_944


def test_stream_matmul_counts_no_tables():
    sm, pm = spec.work("stream_matmul"), spec.work("packed_matmul")
    st = Step(start=0, positions=(5,) * 64)
    assert sm.step(CONF3, st) == pm.step(CONF3, st)
    assert sum(b for _, b in sm.step(CONF3, Step(start=0, positions=())))\
        == 30 * 3_538_944 * 3 / 8 + 30 * 3_538_944 / 32 * 2


def test_model_step_flops():
    ms = spec.work("model_step")
    st = Step(start=0, positions=(0,))
    per_row = 2 * (30 * 3_538_944 + 576 * 49152)
    assert ms.step_flops(CONF, st) == pytest.approx(
        per_row + 4 * 9 * 64 * 30 * 1)
