"""Planner scale-out: warm-start re-planning, pool fan-out, persistent
cache tier, and the LayoutCache internals ISSUE-9 calls out as untested.

Everything here must hold on a 1-core container: the pool path is
exercised by monkeypatching ``os.cpu_count`` (spawned workers run
with 1 core; the processes just time-share), and every speed claim is
checked as *bit-equivalence*, never wall-clock.
"""
import json
import warnings

import numpy as np
import pytest

import repro.core.iris as iris_mod
from repro.core.iris import LayoutCache, schedule, schedule_many
from repro.core.task import ArraySpec, LayoutProblem, make_problem


def _dense_problem(m=64, n=5, seed=0):
    """A gap-free scheduling instance (due dates tight enough that the
    trace has no idle cycles), so warm starts are applicable."""
    rng = np.random.default_rng(seed)
    arrays = tuple(
        ArraySpec(f"a{i}", width=int(rng.integers(2, 9)),
                  depth=int(rng.integers(50, 400)),
                  due=int(rng.integers(1, 40)), max_lanes=None)
        for i in range(n))
    return LayoutProblem(m=m, arrays=arrays)


def _with_depth(prob, idx, delta):
    arrays = list(prob.arrays)
    a = arrays[idx]
    arrays[idx] = ArraySpec(a.name, a.width, a.depth + delta, a.due,
                            a.max_lanes)
    return LayoutProblem(m=prob.m, arrays=tuple(arrays))


# ----------------------------------------------------------------------
# incremental warm-start re-planning
# ----------------------------------------------------------------------
def test_warm_start_sub_bit_identical():
    base = _dense_problem(seed=1)
    cache = LayoutCache()
    schedule(base, cache=cache)
    for delta in (1, 7, -3):
        nxt = _with_depth(base, 2, delta)
        warm = schedule(nxt, cache=cache)
        cold = schedule(nxt, cache=None, warm_start=False)
        assert warm.count_intervals == cold.count_intervals, delta


def test_warm_start_ins_del_bit_identical():
    base = _dense_problem(seed=2)
    cold_base = schedule(base, cache=None)

    # insert an array
    cache = LayoutCache()
    cache.insert(base, False, cold_base)
    arrays = list(base.arrays)
    arrays.insert(2, ArraySpec("new", 4, 120, 10, None))
    p_ins = LayoutProblem(m=base.m, arrays=tuple(arrays))
    assert schedule(p_ins, cache=cache).count_intervals == \
        schedule(p_ins, cache=None, warm_start=False).count_intervals

    # delete an array
    cache = LayoutCache()
    cache.insert(base, False, cold_base)
    arrays = list(base.arrays)
    del arrays[3]
    p_del = LayoutProblem(m=base.m, arrays=tuple(arrays))
    assert schedule(p_del, cache=cache).count_intervals == \
        schedule(p_del, cache=None, warm_start=False).count_intervals


def test_warm_start_counter_and_chaining():
    """Consecutive one-delta neighbors warm off each other (MRU chain).

    Constructed so the warm window is provably gap-free: only ``a0``
    (release 0) is ready before the other arrays release at
    ``R = d_max - due = 9``, and its depth alone covers those cycles, so
    the prefix reuse is always applicable (the idle-gap safety check
    cannot bail).
    """
    base = make_problem(64, [("a0", 4, 200, 10), ("a1", 8, 60, 1),
                             ("a2", 2, 150, 1), ("a3", 6, 80, 1)])
    cache = LayoutCache()
    schedule(base, cache=cache)
    for i in range(1, 4):
        p = _with_depth(base, 1, i)
        warm = schedule(p, cache=cache)
        assert warm.count_intervals == \
            schedule(p, cache=None, warm_start=False).count_intervals
    assert cache.warm_starts == 3
    assert cache.stats["warm_starts"] == 3


def test_warm_start_requires_same_bus_width():
    base = _dense_problem(seed=4)
    cache = LayoutCache()
    schedule(base, cache=cache)
    wider = LayoutProblem(m=base.m * 2, arrays=base.arrays)
    lay = schedule(wider, cache=cache)       # cold: no usable neighbor
    assert cache.warm_starts == 0
    assert lay.count_intervals == schedule(wider, cache=None).count_intervals


def test_warm_start_disabled_flag():
    base = _dense_problem(seed=5)
    cache = LayoutCache()
    schedule(base, cache=cache)
    nxt = _with_depth(base, 1, 2)
    schedule(nxt, cache=cache, warm_start=False)
    assert cache.warm_starts == 0


# ----------------------------------------------------------------------
# LayoutCache internals: LRU order, stats counters
# ----------------------------------------------------------------------
def test_lru_eviction_respects_lookup_promotion():
    cache = LayoutCache(maxsize=3)
    probs = [make_problem(8, [("a", 2, d, 0)]) for d in (3, 4, 5, 6, 7)]
    for p in probs[:3]:
        schedule(p, cache=cache)
    cache.lookup(probs[0])                   # promote p0 over p1, p2
    schedule(probs[3], cache=cache)          # evicts p1 (now LRU)
    schedule(probs[4], cache=cache)          # evicts p2
    assert cache.lookup(probs[0]) is not None
    assert cache.lookup(probs[3]) is not None
    assert cache.lookup(probs[4]) is not None
    assert cache.lookup(probs[1]) is None and cache.lookup(probs[2]) is None
    assert len(cache) == 3


def test_stats_counters_across_schedule_many():
    layers = [make_problem(32, [("w", 4, 60, 5)]) for _ in range(4)]
    distinct = make_problem(32, [("w", 4, 61, 5)])
    cache = LayoutCache()
    schedule_many(layers + [distinct], cache=cache, workers=1)
    s = cache.stats
    assert s["misses"] == 2 and s["hits"] == 3 and s["size"] == 2
    # a second pass is all hits
    schedule_many(layers, cache=cache, workers=1)
    assert cache.stats["hits"] == 7 and cache.stats["misses"] == 2


def test_stats_parity_serial_vs_pool(monkeypatch):
    probs = [_dense_problem(seed=s) for s in range(5)] * 2
    serial = LayoutCache()
    outs_s = schedule_many(probs, cache=serial, workers=1)
    monkeypatch.setattr(iris_mod.os, "cpu_count", lambda: 4)
    pooled = LayoutCache()
    outs_p = schedule_many(probs, cache=pooled, workers=2)
    assert all(a.count_intervals == b.count_intervals
               for a, b in zip(outs_s, outs_p))
    assert (serial.stats["hits"], serial.stats["misses"]) == \
        (pooled.stats["hits"], pooled.stats["misses"])


def test_pool_failure_falls_back_to_serial(monkeypatch):
    probs = [_dense_problem(seed=s) for s in range(3)]
    expect = [schedule(p, cache=None).count_intervals for p in probs]
    monkeypatch.setattr(iris_mod.os, "cpu_count", lambda: 4)
    monkeypatch.setattr(iris_mod, "_pool_schedule",
                        lambda *a, **k: None)   # pool unavailable
    outs = schedule_many(probs, cache=LayoutCache(), workers=2)
    assert [o.count_intervals for o in outs] == expect


def test_effective_workers_clamps():
    real = iris_mod.os.cpu_count() or 1
    assert iris_mod._effective_workers(8, 2) <= 2
    assert iris_mod._effective_workers(8, 100) <= real
    assert iris_mod._effective_workers(None, 1) == 1
    assert iris_mod._effective_workers(0, 5) == 1


# ----------------------------------------------------------------------
# persistent tier
# ----------------------------------------------------------------------
def test_persistent_roundtrip_fresh_cache(tmp_path):
    prob = _dense_problem(seed=7)
    writer = LayoutCache(cache_dir=tmp_path)
    lay = schedule(prob, cache=writer)
    reader = LayoutCache(cache_dir=tmp_path)
    hit = reader.lookup(prob)
    assert hit is not None
    assert hit.count_intervals == lay.count_intervals
    assert reader.disk_hits == 1 and reader.hits == 1 and reader.misses == 0
    # promoted to memory: second lookup does not touch disk again
    reader.lookup(prob)
    assert reader.disk_hits == 1 and reader.hits == 2


def test_persistent_keys_on_fill_residual(tmp_path):
    prob = _dense_problem(seed=8)
    writer = LayoutCache(cache_dir=tmp_path)
    schedule(prob, cache=writer, fill_residual=True)
    reader = LayoutCache(cache_dir=tmp_path)
    assert reader.lookup(prob, fill_residual=False) is None
    assert reader.lookup(prob, fill_residual=True) is not None


def _entry_path(tmp_path):
    paths = list(tmp_path.glob("*.json"))
    assert len(paths) == 1
    return paths[0]


def _reject(tmp_path, prob):
    cache = LayoutCache(cache_dir=tmp_path)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        out = cache.lookup(prob)
    assert out is None
    assert cache.disk_rejects == 1 and cache.misses == 1
    return cache


def test_disk_rejects_digest_mismatch(tmp_path):
    prob = _dense_problem(seed=9)
    schedule(prob, cache=LayoutCache(cache_dir=tmp_path))
    path = _entry_path(tmp_path)
    obj = json.loads(path.read_text())
    obj["payload"]["intervals"][0][0] += 1     # digest now stale
    path.write_text(json.dumps(obj))
    _reject(tmp_path, prob)
    assert not path.exists(), "corrupt entry must be unlinked"


def test_disk_rejects_coverage_gap_via_analysis_gate(tmp_path):
    """A consistent-digest entry with the mutation harness's
    ``coverage-gap`` defect must die at the verification gate, not at the
    digest check — the same fault class ``corrupt_checkpoint`` plants."""
    from repro.analysis.mutations import corrupt_checkpoint

    prob = _dense_problem(seed=10)
    schedule(prob, cache=LayoutCache(cache_dir=tmp_path))
    path = _entry_path(tmp_path)
    obj = json.loads(path.read_text())
    mutated, _s, _d = corrupt_checkpoint(
        {"intervals": obj["payload"]["intervals"]},
        np.zeros((1, 1, 8), dtype=np.uint8), "", "coverage-gap")
    obj["payload"]["intervals"] = mutated["intervals"]
    obj["sha256"] = LayoutCache._payload_digest(obj["payload"])
    path.write_text(json.dumps(obj))
    _reject(tmp_path, prob)


def test_disk_rejects_non_canonical_run(tmp_path):
    prob = _dense_problem(seed=11)
    schedule(prob, cache=LayoutCache(cache_dir=tmp_path))
    path = _entry_path(tmp_path)
    obj = json.loads(path.read_text())
    obj["payload"]["intervals"][0][1].append([0, 0])   # zero-count slot
    obj["sha256"] = LayoutCache._payload_digest(obj["payload"])
    path.write_text(json.dumps(obj))
    _reject(tmp_path, prob)


def test_disk_rejects_truncated_json(tmp_path):
    prob = _dense_problem(seed=12)
    schedule(prob, cache=LayoutCache(cache_dir=tmp_path))
    path = _entry_path(tmp_path)
    path.write_text(path.read_text()[:80])
    _reject(tmp_path, prob)
    assert not path.exists()


def test_disk_rejects_signature_mismatch(tmp_path):
    """An entry filed under one key whose payload describes a different
    problem (e.g. a collision or a copied file) is rejected."""
    p1 = _dense_problem(seed=13)
    p2 = _with_depth(p1, 0, 5)
    schedule(p1, cache=LayoutCache(cache_dir=tmp_path))
    schedule(p2, cache=LayoutCache(cache_dir=tmp_path))
    a, b = sorted(tmp_path.glob("*.json"))
    b_text = b.read_text()
    a.write_text(b_text)                       # a's key, b's payload
    cache = LayoutCache(cache_dir=tmp_path)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        one = cache.lookup(p1)
        two = cache.lookup(p2)
    # exactly one of the two keys had the wrong payload under it
    assert cache.disk_rejects == 1
    assert (one is None) != (two is None)


def test_evicted_entry_survives_on_disk(tmp_path):
    """Memory-tier eviction must not forget what the disk knows."""
    cache = LayoutCache(maxsize=1, cache_dir=tmp_path)
    p1 = _dense_problem(seed=14)
    p2 = _with_depth(p1, 1, 3)
    lay1 = schedule(p1, cache=cache)
    schedule(p2, cache=cache)                  # evicts p1 from memory
    assert len(cache) == 1
    hit = cache.lookup(p1)                     # re-promoted from disk
    assert hit is not None
    assert hit.count_intervals == lay1.count_intervals
    assert cache.disk_hits == 1


def test_clear_resets_all_counters(tmp_path):
    cache = LayoutCache(cache_dir=tmp_path)
    prob = _dense_problem(seed=15)
    schedule(prob, cache=cache)
    schedule(prob, cache=cache)
    cache.clear()
    assert cache.stats == {"hits": 0, "misses": 0, "size": 0,
                           "maxsize": 256, "warm_starts": 0,
                           "disk_hits": 0, "disk_rejects": 0}


# ----------------------------------------------------------------------
# DEFAULT_CACHE env configuration
# ----------------------------------------------------------------------
def test_env_default_cache_size(monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_SIZE", "17")
    monkeypatch.delenv("REPRO_CACHE_DIR", raising=False)
    c = iris_mod._env_default_cache()
    assert c.maxsize == 17 and c.cache_dir is None


def test_env_default_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "layouts"))
    monkeypatch.delenv("REPRO_CACHE_SIZE", raising=False)
    c = iris_mod._env_default_cache()
    assert c.maxsize == 512
    assert c.cache_dir is not None
    prob = _dense_problem(seed=16)
    schedule(prob, cache=c)
    assert list(c.cache_dir.glob("*.json")), "persistent tier not active"


def test_env_default_cache_malformed_size(monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_SIZE", "not-a-number")
    monkeypatch.delenv("REPRO_CACHE_DIR", raising=False)
    assert iris_mod._env_default_cache().maxsize == 512


# ----------------------------------------------------------------------
# DSE sweep through the batch scheduler
# ----------------------------------------------------------------------
def test_sweep_strategies_matches_per_problem_compare():
    from repro import api
    from repro.core.dse import sweep_strategies

    probs = [_dense_problem(seed=s) for s in range(3)]
    swept = sweep_strategies(probs, ("iris",), cache=LayoutCache())
    for p, row in zip(probs, swept):
        ref = api.compare(p, strategies=("iris",), cache=None)
        assert row["iris"].c_max == ref["iris"].c_max
        assert row["iris"].efficiency == ref["iris"].efficiency


def test_sweep_strategies_presolves_into_cache():
    from repro.core.dse import sweep_strategies

    probs = [_dense_problem(seed=s) for s in (20, 21)]
    cache = LayoutCache()
    sweep_strategies(probs, ("iris",), cache=cache)
    # the compare loop ran on cache hits: one miss per unique signature
    assert cache.misses == len(probs)
    assert cache.hits >= len(probs)
