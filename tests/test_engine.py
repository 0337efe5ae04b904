"""repro.engine: queue admission, scheduler invariants, stream uploads,
metrics, and the bit-identity contract vs single-stream serving.

The expensive model-backed tests (packed trees, interpret-mode Pallas)
share one session fixture and keep request counts tiny; everything else
runs on a no-JAX stub adapter so queue/scheduler/metrics semantics are
exercised at Python speed (including the hypothesis fairness property).
"""
from __future__ import annotations

import copy

import numpy as np
import pytest

from repro.engine import (
    REJECT_BACKLOG_FULL,
    REJECT_DEADLINE_EXPIRED,
    AdmissionQueue,
    BufferRing,
    Engine,
    EngineConfig,
    EngineMetrics,
    EngineRequest,
    greedy_sampler,
    percentile,
)
from repro.engine.scheduler import UPLOAD_COUNTERS

try:
    import hypothesis
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


# ----------------------------------------------------------------------
# stub adapter: scheduler semantics without a model
# ----------------------------------------------------------------------
class StubAdapter:
    """Deterministic no-JAX adapter: logits one-hot the slot's last
    token + 1 (mod vocab), so generated streams are predictable."""

    vocab = 16

    def __init__(self) -> None:
        self.reset_calls: list[int] = []
        self.step_actives: list[list[int]] = []

    def init_state(self, batch_size: int, max_seq: int) -> dict:
        return {"batch": batch_size}

    def reset_slot(self, state: dict, i: int) -> None:
        self.reset_calls.append(i)

    def step(self, state, tokens, active):
        self.step_actives.append(list(active))
        logits = np.zeros((len(active), self.vocab), np.float32)
        for j, t in enumerate(np.asarray(tokens)):
            logits[j, (int(t) + 1) % self.vocab] = 1.0
        return logits, state


def _stub_engine(batch=2, max_seq=64, **cfg_kw) -> Engine:
    return Engine(StubAdapter(), EngineConfig(batch_size=batch,
                                              max_seq=max_seq, **cfg_kw))


def _reqs(n, *, prompt_len=2, max_new=3, **kw):
    return [EngineRequest(uid=i, prompt=list(range(1, 1 + prompt_len)),
                          max_new_tokens=max_new, **kw) for i in range(n)]


# ----------------------------------------------------------------------
# admission queue
# ----------------------------------------------------------------------
class TestAdmissionQueue:
    def test_backlog_overflow_rejects_with_reason(self):
        q = AdmissionQueue(max_backlog=2, clock=lambda: 0.0)
        assert q.submit(EngineRequest(0, [1], 1))
        assert q.submit(EngineRequest(1, [1], 1))
        adm = q.submit(EngineRequest(2, [1], 1))
        assert not adm
        assert adm.reason == REJECT_BACKLOG_FULL
        assert q.rejected_by_reason == {REJECT_BACKLOG_FULL: 1}
        assert len(q) == 2

    def test_deadline_expiry_at_submit_and_pop(self):
        t = [0.0]
        q = AdmissionQueue(clock=lambda: t[0])
        late = EngineRequest(0, [1], 1, deadline=-1.0)
        adm = q.submit(late)
        assert not adm and adm.reason == REJECT_DEADLINE_EXPIRED
        assert late.status == "rejected"
        # expires while waiting: rejected lazily at pop
        q.submit(EngineRequest(1, [1], 1, deadline=5.0))
        q.submit(EngineRequest(2, [1], 1))
        t[0] = 10.0
        got = q.pop()
        assert got is not None and got.uid == 2
        assert (1, REJECT_DEADLINE_EXPIRED) in q.rejections

    def test_priority_then_fifo(self):
        q = AdmissionQueue(clock=lambda: 0.0)
        for uid, pri in [(0, 0), (1, 5), (2, 0), (3, 5)]:
            q.submit(EngineRequest(uid, [1], 1, priority=pri))
        assert [q.pop().uid for _ in range(4)] == [1, 3, 0, 2]

    def test_drain_expired(self):
        t = [0.0]
        q = AdmissionQueue(clock=lambda: t[0])
        q.submit(EngineRequest(0, [1], 1, deadline=1.0))
        q.submit(EngineRequest(1, [1], 1))
        t[0] = 2.0
        assert q.drain_expired() == 1
        assert len(q) == 1 and q.pop().uid == 1

    if HAVE_HYPOTHESIS:
        @hypothesis.given(st.lists(st.integers(0, 3), min_size=1,
                                   max_size=30))
        def test_fairness_priority_then_arrival_order(self, priorities):
            """Admission (pop) order is exactly (priority desc, arrival
            asc) — equal-priority requests are never reordered."""
            q = AdmissionQueue(max_backlog=None, clock=lambda: 0.0)
            for uid, pri in enumerate(priorities):
                q.submit(EngineRequest(uid, [1], 1, priority=pri))
            popped = [q.pop().uid for _ in range(len(priorities))]
            expect = [uid for _, uid in
                      sorted(((-p, uid) for uid, p in enumerate(priorities)))]
            assert popped == expect


# ----------------------------------------------------------------------
# scheduler semantics (stub adapter)
# ----------------------------------------------------------------------
class TestEngineScheduler:
    def test_slot_reuse_and_completion(self):
        eng = _stub_engine(batch=2)
        reqs = _reqs(5)
        for r in reqs:
            assert eng.submit(r)
        stats = eng.run_until_drained()
        assert stats.completed == 5 and stats.admitted == 5
        assert stats.tokens_generated == sum(r.max_new_tokens for r in reqs)
        assert eng.slots == [None, None] and not eng.queue
        assert all(r.done and r.status == "done" for r in reqs)
        # both slots were reused (5 admissions into 2 slots)
        assert len(eng.adapter.reset_calls) == 5
        assert set(eng.adapter.reset_calls) == {0, 1}

    def test_fifo_admission_order(self):
        eng = _stub_engine(batch=2)
        for r in _reqs(6):
            eng.submit(r)
        eng.run_until_drained()
        assert eng.admission_order == list(range(6))

    def test_active_set_never_exceeds_batch(self):
        eng = _stub_engine(batch=3)
        for r in _reqs(8, max_new=2):
            eng.submit(r)
        eng.run_until_drained()
        assert all(len(a) <= 3 for a in eng.adapter.step_actives)
        assert max(len(a) for a in eng.adapter.step_actives) == 3

    def test_static_policy_drains_batch_before_admitting(self):
        eng = _stub_engine(batch=2, policy="static")
        for r in _reqs(4):
            eng.submit(r)
        admits_when_busy = []
        eng.add_hook("admit", lambda e, s, ctx:
                     admits_when_busy.append((len(ctx.get("admitted", [])),
                                              e.n_active)))
        eng.run_until_drained()
        assert eng.stats.completed == 4
        # whenever the batch held leftover actives, nothing was admitted
        for n_admitted, n_active in admits_when_busy:
            if n_admitted:
                assert n_active == n_admitted  # only into an empty batch

    def test_continuous_policy_backfills_freed_slots(self):
        eng = _stub_engine(batch=2)
        short = EngineRequest(0, [1], 1)
        long = EngineRequest(1, [1], 8)
        queued = EngineRequest(2, [1], 1)
        for r in (short, long, queued):
            eng.submit(r)
        eng.run_until_drained()
        # uid 2 backfilled uid 0's freed slot while uid 1 still ran:
        # it finished before uid 1 and shared at least one step with it
        assert eng.completion_order == [0, 2, 1]
        assert any(len(a) == 2 for a in eng.adapter.step_actives[1:])

    def test_engine_rejects_feed_metrics(self):
        eng = _stub_engine(batch=1, max_backlog=1)
        eng.submit(EngineRequest(0, [1], 4))
        eng.step()                         # uid 0 occupies the only slot
        eng.submit(EngineRequest(1, [1], 1))
        adm = eng.submit(EngineRequest(2, [1], 1))
        assert not adm and adm.reason == REJECT_BACKLOG_FULL
        eng.run_until_drained()
        snap = eng.metrics.snapshot()
        assert snap["requests"]["rejected"] == 1
        assert snap["requests"]["rejected_by_reason"] == {
            REJECT_BACKLOG_FULL: 1}
        assert snap["requests"]["completed"] == 2

    def test_max_seq_guard_completes_request(self):
        eng = _stub_engine(batch=1, max_seq=4)
        r = EngineRequest(0, [1, 2], max_new_tokens=50)
        eng.submit(r)
        eng.run_until_drained()
        assert r.done and len(r.generated) < 50

    def test_eos_token_stops_generation(self):
        # stub emits (last_token + 1) % 16; prompt [1] -> 2, 3, 4, ...
        eng = Engine(StubAdapter(), EngineConfig(batch_size=1, max_seq=64,
                                                 eos_token=4))
        r = EngineRequest(0, [1], max_new_tokens=50)
        eng.submit(r)
        eng.run_until_drained()
        assert r.generated[-1] == 4 and len(r.generated) == 3


class TestSampler:
    def test_greedy_sampler_requires_single_row(self):
        """The per-slot contract: a batched logits matrix must be
        refused, not argmax'd across slots (which would return an index
        into B*V — another slot's token scaled out of vocab range)."""
        with pytest.raises(ValueError, match="one slot's logits row"):
            greedy_sampler(np.zeros((2, 16), np.float32),
                           EngineRequest(0, [1], 1))

    def test_engine_samples_per_slot(self):
        """Every sampler call sees exactly one 1-D row and its own
        request, and every sampled token is in vocab range."""
        seen = []

        def sampler(row, req):
            row = np.asarray(row)
            assert row.ndim == 1 and row.shape[0] == StubAdapter.vocab
            seen.append(req.uid)
            return int(row.argmax())

        eng = Engine(StubAdapter(), EngineConfig(batch_size=2, max_seq=64),
                     sampler=sampler)
        reqs = _reqs(4)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert set(seen) == {0, 1, 2, 3}
        for r in reqs:
            assert all(0 <= t < StubAdapter.vocab for t in r.generated)

    def test_stub_streams_are_per_slot_not_flattened(self):
        """Two concurrent slots generate their own deterministic
        streams: (tok+1) mod vocab chains from each request's prompt."""
        eng = _stub_engine(batch=2)
        a = EngineRequest(0, [3], max_new_tokens=3)
        b = EngineRequest(1, [9], max_new_tokens=3)
        eng.submit(a)
        eng.submit(b)
        eng.run_until_drained()
        assert a.generated == [4, 5, 6]
        assert b.generated == [10, 11, 12]


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------
class TestMetrics:
    def test_percentile_matches_numpy(self):
        xs = [5.0, 1.0, 9.0, 3.0, 7.5, 2.2]
        for p in (0, 25, 50, 90, 99, 100):
            assert percentile(xs, p) == pytest.approx(
                float(np.percentile(xs, p)))

    def test_snapshot_schema_and_phases(self):
        t = [0.0]
        m = EngineMetrics(clock=lambda: t[0])
        m.record_submit(0)
        t[0] = 1.0
        m.record_admit(0)
        t[0] = 3.0
        m.record_first_token(0)
        m.record_token(0)
        t[0] = 6.0
        m.record_token(0)
        m.record_complete(0)
        m.record_step(2)
        snap = m.snapshot()
        assert set(snap) == {"requests", "latency", "throughput"}
        assert set(snap["latency"]) == {"queue", "prefill", "decode",
                                        "total"}
        assert snap["latency"]["queue"]["p50_s"] == 1.0
        assert snap["latency"]["prefill"]["p50_s"] == 2.0
        assert snap["latency"]["decode"]["p50_s"] == 3.0
        assert snap["latency"]["total"]["p50_s"] == 6.0
        thr = snap["throughput"]
        assert thr["tokens_generated"] == 2
        assert thr["mean_batch_occupancy"] == 2.0
        assert thr["goodput_tokens_per_s"] == pytest.approx(2 / 6.0)

    def test_to_json_roundtrip(self, tmp_path):
        import json

        m = EngineMetrics()
        m.record_submit(0)
        p = tmp_path / "m.json"
        m.to_json(str(p))
        assert json.loads(p.read_text())["requests"]["submitted"] == 1


# ----------------------------------------------------------------------
# buffer ring / uploader (model-free parts)
# ----------------------------------------------------------------------
class TestBufferRing:
    def test_fifo_eviction_at_depth(self):
        r = BufferRing(depth=2)
        r.put("a", 1)
        r.put("b", 2)
        r.put("c", 3)
        assert r.keys() == ["b", "c"] and r.evictions == 1
        assert r.get("a") is None and r.get("c") == 3

    def test_reput_moves_to_end_without_eviction(self):
        r = BufferRing(depth=2)
        r.put("a", 1)
        r.put("b", 2)
        r.put("a", 10)
        assert r.keys() == ["b", "a"] and r.evictions == 0


# ----------------------------------------------------------------------
# legacy wrapper
# ----------------------------------------------------------------------
class TestServeLoopDeprecation:
    def test_names_warn_and_resolve(self):
        import repro.runtime.serve_loop as sl

        with pytest.warns(DeprecationWarning, match="repro.engine.Engine"):
            loop_cls = sl.ServeLoop
        assert loop_cls is sl._ServeLoop
        with pytest.warns(DeprecationWarning,
                          match="repro.engine.EngineRequest"):
            req_cls = sl.Request
        assert req_cls is EngineRequest
        with pytest.raises(AttributeError):
            sl.does_not_exist


# ----------------------------------------------------------------------
# model-backed: packed trees, bit-identity, uploader equivalence
# ----------------------------------------------------------------------
@pytest.fixture(scope="session")
def packed_setup():
    import jax

    from repro import api
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.quant import QuantSpec

    cfg = get_config("smollm-135m").reduced(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=128)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    trees = {bits: api.pack_tree(cfg, params,
                                 QuantSpec(bits=bits, group_size=32), m=512)
             for bits in (3, 4)}
    return cfg, model, trees


def _oracle_tokens(cfg, model, tree, req):
    """Single-stream reference: the request served alone, batch=1,
    plain full-batch ``packed_decode_step`` — engine-independent."""
    import jax.numpy as jnp

    from repro.models.quantized import packed_decode_step

    state = model.init_decode_state(1, 32)
    generated: list[int] = []
    pos = 0
    while len(generated) < req.max_new_tokens and pos < 31:
        tok = req.prompt[pos] if pos < len(req.prompt) else generated[-1]
        logits, state = packed_decode_step(
            cfg, tree, state, jnp.asarray([tok], jnp.int32))
        pos += 1
        if pos >= len(req.prompt):
            generated.append(int(np.asarray(logits[0]).argmax()))
    return generated


@pytest.mark.parametrize("bits", [3, 4])
def test_engine_tokens_bit_identical_to_single_stream(packed_setup, bits):
    """Continuous batching must not change a single token: the engine's
    ragged multi-slot decode equals serving each request alone."""
    from repro.engine import PackedAdapter

    cfg, model, trees = packed_setup
    tree = trees[bits]
    reqs = [EngineRequest(uid=0, prompt=[5, 9], max_new_tokens=2),
            EngineRequest(uid=1, prompt=[17, 3, 8], max_new_tokens=3),
            EngineRequest(uid=2, prompt=[40], max_new_tokens=2)]
    eng = Engine(PackedAdapter(cfg, tree),
                 EngineConfig(batch_size=2, max_seq=32))
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert stats.completed == 3
    for r in reqs:
        want = _oracle_tokens(cfg, model, tree,
                              copy.deepcopy(
                                  EngineRequest(r.uid, r.prompt,
                                                r.max_new_tokens)))
        assert r.generated == want, f"uid={r.uid} bits={bits}"


def test_ragged_step_rows_bit_identical_to_full_batch(packed_setup):
    """packed_decode_step(slot_ids=...) computes exactly the full-batch
    values for the selected rows, and only those rows' clocks advance."""
    import jax.numpy as jnp

    from repro.models.quantized import packed_decode_step

    cfg, model, trees = packed_setup
    tree = trees[3]
    state = model.init_decode_state(4, 16)
    full, _ = packed_decode_step(cfg, tree, state,
                                 jnp.asarray([5, 6, 7, 8], jnp.int32))
    ragged, st = packed_decode_step(cfg, tree, state,
                                    jnp.asarray([6, 8], jnp.int32),
                                    slot_ids=jnp.asarray([1, 3], jnp.int32))
    assert (np.asarray(full)[[1, 3]] == np.asarray(ragged)).all()
    assert np.asarray(st["pos"]).tolist() == [0, 1, 0, 1]


def test_stream_uploader_matches_resident_buffers(packed_setup):
    """The uploader hands back word-for-word the tree's own stream
    views, and its prefetch/ring counters reflect the double-buffering."""
    from repro.engine import StreamUploader

    cfg, model, trees = packed_setup
    tree = trees[3]
    with StreamUploader(tree) as up:
        for layer in range(tree.n_layers):
            got = np.asarray(up(layer))
            want = np.asarray(tree.layer_stream_words(layer))
            assert (got == want).all()
        # second lap: every fetch is a prefetch hit
        hits0 = up.prefetch_hits
        for layer in range(tree.n_layers):
            up(layer)
        assert up.prefetch_hits >= hits0 + tree.n_layers
        assert up.uploads <= 2 * tree.n_layers
        s = up.stats()
        assert s["bytes_uploaded"] > 0 and s["ring_depth"] == 2


def test_stream_uploader_requires_stream_buffers(packed_setup):
    from repro import api
    from repro.engine import StreamUploader
    from repro.quant import QuantSpec

    import jax

    cfg, model, trees = packed_setup
    params = model.init(jax.random.PRNGKey(0))
    bare = api.pack_tree(cfg, params, QuantSpec(bits=4, group_size=32),
                         m=512, with_streams=False)
    with pytest.raises(ValueError, match="with_streams=False"):
        StreamUploader(bare)


def test_engine_with_uploader_bit_identical(packed_setup):
    """Stream uploads through the ring change nothing about the math."""
    from repro.engine import PackedAdapter, StreamUploader

    cfg, model, trees = packed_setup
    tree = trees[3]

    def run(uploader):
        reqs = [EngineRequest(uid=0, prompt=[5, 9], max_new_tokens=2),
                EngineRequest(uid=1, prompt=[17, 3], max_new_tokens=2)]
        eng = Engine(PackedAdapter(cfg, tree, uploader=uploader),
                     EngineConfig(batch_size=2, max_seq=32))
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return [r.generated for r in reqs], eng

    base, _ = run(None)
    with StreamUploader(tree) as up:
        uploaded, eng = run(up)
        mid = up.stats()
        again, eng2 = run(up)
    end = up.stats()                    # closed: no upload in flight
    assert uploaded == base == again
    # upload accounting flowed into the metrics, one engine at a time:
    # the second engine does not count the first one's uploads
    first, second = (e.metrics.snapshot()["throughput"] for e in (eng, eng2))
    for got in (first, second):
        assert set(got["uploader"]) == set(UPLOAD_COUNTERS)
        assert got["uploader"]["prefetch_hits"] > 0
        assert got["stream_bytes"] == got["uploader"]["bytes_uploaded"]
    assert first["uploader"]["uploads"] > 0 and first["stream_bytes"] > 0
    for k in UPLOAD_COUNTERS:
        assert first["uploader"][k] <= mid[k]
        assert second["uploader"][k] <= end[k] - mid[k]
    assert second["uploader"]["uploads"] < end["uploads"]


class CountingUploaderAdapter(StubAdapter):
    """A stub whose uploader has a lifetime before the engine and
    counts 3 uploads of 100 bytes, 2 prefetch hits and 1 synchronous
    fetch per step."""

    def __init__(self) -> None:
        super().__init__()
        self.counters = {"uploads": 7, "bytes_uploaded": 700,
                         "prefetch_hits": 5, "sync_fetches": 2,
                         "ring_depth": 2, "ring_evictions": 4}

    def step(self, state, tokens, active):
        for k, n in (("uploads", 3), ("bytes_uploaded", 300),
                     ("prefetch_hits", 2), ("sync_fetches", 1),
                     ("ring_evictions", 3)):
            self.counters[k] += n
        return super().step(state, tokens, active)

    def uploader_stats(self):
        return dict(self.counters)


def test_engine_records_each_steps_upload_counters():
    """The engine adds the uploader's change over each decode stage:
    the snapshot counts this engine's uploads, not the uploader's
    lifetime, and carries the counters alone (no ring depth)."""
    eng = Engine(CountingUploaderAdapter(),
                 EngineConfig(batch_size=2, max_seq=64))
    for r in _reqs(2, prompt_len=1, max_new=3):
        eng.submit(r)
    eng.run_until_drained()
    steps = eng.metrics.steps
    assert steps == 3
    got = eng.metrics.snapshot()["throughput"]
    assert got["uploader"] == {"uploads": 3 * steps,
                               "bytes_uploaded": 300 * steps,
                               "prefetch_hits": 2 * steps,
                               "sync_fetches": steps}
    assert got["stream_bytes"] == 300 * steps


def test_engine_without_uploader_snapshot_has_empty_uploader_dict():
    snap = _stub_engine().metrics.snapshot()
    assert snap["throughput"]["uploader"] == {}


def test_engine_resets_fallback_warning_state():
    """Constructing an Engine clears the once-per-process host-fallback
    warning sets in *both* kernel modules, so a fresh serving run warns
    again instead of inheriting a stale silence."""
    from repro.kernels import layout_decode, layout_pack

    layout_decode._FALLBACK_WARNED.add(("stale", "w"))
    layout_pack._FALLBACK_WARNED.add(("stale", "w"))
    _stub_engine()
    assert not layout_decode._FALLBACK_WARNED
    assert not layout_pack._FALLBACK_WARNED


@pytest.mark.parametrize("bits", [3, 4])
def test_engine_packed_kv_stream_bit_identical_to_dense_oracle(
        packed_setup, bits):
    """Engine-level KV acceptance gate: serving on the packed KV cache
    with the stream-direct attention kernel produces tokens
    bit-identical to the materialized dense-dequant oracle over the same
    pages, across ragged admission (3 requests on 2 slots), and the
    appends never touch the planner."""
    from repro.core.iris import DEFAULT_CACHE
    from repro.engine import PackedAdapter

    cfg, model, trees = packed_setup
    tree = trees[bits]

    def run(kv_attention):
        reqs = [EngineRequest(uid=0, prompt=[5, 9], max_new_tokens=2),
                EngineRequest(uid=1, prompt=[17, 3, 8], max_new_tokens=3),
                EngineRequest(uid=2, prompt=[40], max_new_tokens=2)]
        eng = Engine(PackedAdapter(cfg, tree, kv="packed",
                                   kv_attention=kv_attention,
                                   page_tokens=8),
                     EngineConfig(batch_size=2, max_seq=32))
        for r in reqs:
            eng.submit(r)
        stats = eng.run_until_drained()
        assert stats.completed == 3
        return [r.generated for r in reqs], eng

    stream, eng = run("stream")
    kvc = eng.state["packed_kv"]
    assert kvc.plan_stats["scheduler_runs"] <= 1
    misses0 = DEFAULT_CACHE.misses
    dense, _ = run("dense")
    assert stream == dense
    # the whole second serve (create + every append) re-used the layout
    assert DEFAULT_CACHE.misses == misses0
