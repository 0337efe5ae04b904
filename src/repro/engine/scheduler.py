"""Stage-decoupled continuous-batching scheduler over packed weights.

The engine drives a fixed pool of decode *slots* through four explicit
stages every step — the event-driven issue/commit split of a hardware
pipeline, in host Python:

    admit      queue -> free slots (continuous: whenever a slot frees;
               static: only when the whole batch drained — the baseline
               bench_serve.py compares against)
    prefill    assemble the ragged token batch: prompt-phase slots feed
               their next prompt token, decode-phase slots feed their
               last sampled token
    decode     one adapter step over the *active* rows only (ragged M —
               the packed kernels pad internally, so a half-empty batch
               costs a half-size matmul, not a full one)
    retire     per-slot sampling, completion checks, slot release

Each stage is an overridable method with observation hooks
(:meth:`Engine.add_hook`), so admission policies, samplers and schedulers
swap without forking the loop.  Per-request timing flows into
:class:`~repro.engine.metrics.EngineMetrics` at every stage boundary.

Model access goes through an *adapter* so the engine is arch-agnostic:

* :class:`DenseAdapter` — ``Model.decode_step`` over the full slot batch
  (any family: dense/ssm/rwkv/moe), jitted once; the legacy
  ``ServeLoop`` semantics.
* :class:`PackedAdapter` — ``packed_decode_step`` over a
  :class:`~repro.tree.PackedTree`, stepping only the active rows
  (``slot_ids``) and optionally pulling per-layer stream words through a
  :class:`~repro.engine.streams.StreamUploader` so host->device uploads
  overlap decode.

Per-slot math is row-independent in every step path (matmuls, norms,
attention over per-row caches), so tokens generated under continuous
batching are **bit-identical** to a single-stream run of the same
request — the invariant tests/test_engine.py and bench_serve.py enforce.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Sequence

import numpy as np

from .metrics import EngineMetrics
from .queue import Admission, AdmissionQueue, EngineRequest
from .trace import span, step_span

__all__ = [
    "DenseAdapter", "Engine", "EngineConfig", "PackedAdapter",
    "ServeStats", "greedy_sampler",
]

#: engine stages, in execution order
STAGES = ("admit", "prefill", "decode", "retire")
#: the :meth:`StreamUploader.stats` counters the engine accumulates
UPLOAD_COUNTERS = ("uploads", "bytes_uploaded", "prefetch_hits",
                   "sync_fetches")


def greedy_sampler(logits_row, request: EngineRequest) -> int:
    """Argmax over one slot's vocab row.

    The sampler contract is *per slot*: the engine hands each sampler
    call exactly one request's logits row.  The pre-engine loop's
    default sampler computed ``argmax`` over whatever array it was
    handed — flattened across the batch that returns an index into
    ``B*V``, i.e. another slot's token scaled out of vocab range — so
    this one refuses anything but a single row.
    """
    row = np.asarray(logits_row)
    if row.ndim != 1:
        raise ValueError(
            f"sampler expects one slot's logits row (1-D), got shape "
            f"{row.shape}; per-slot sampling is the engine's contract"
        )
    return int(row.argmax())


@dataclasses.dataclass
class ServeStats:
    """Legacy counter block (``runtime.serve_loop`` compatibility)."""

    steps: int = 0
    tokens_generated: int = 0
    completed: int = 0
    admitted: int = 0


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine knobs."""

    batch_size: int
    max_seq: int
    #: queue capacity (None = unbounded, the legacy contract)
    max_backlog: int | None = 64
    #: "continuous" refills slots as they free; "static" waits for the
    #: whole batch to drain (the baseline continuous batching beats)
    policy: str = "continuous"
    eos_token: int | None = None

    def __post_init__(self) -> None:
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.policy not in ("continuous", "static"):
            raise ValueError(
                f"policy must be 'continuous' or 'static', got {self.policy!r}"
            )


# ----------------------------------------------------------------------
# model adapters
# ----------------------------------------------------------------------
def _reset_state_slot(state: dict, i: int) -> None:
    """Zero slot ``i``'s clock and recurrent state in place.  Dense KV
    caches need no clearing: the per-row position mask hides stale
    entries.  Packed KV pages *are* cleared so page digests (and the
    checkpoint bytes built from them) are deterministic regardless of
    which request previously occupied the slot."""
    state["pos"] = state["pos"].at[i].set(0)
    if "packed_kv" in state:
        state["packed_kv"] = state["packed_kv"].reset(i)
    if "ssm" in state:
        state["ssm"] = state["ssm"].at[:, :, i].set(0.0)
    if "rwkv" in state:
        state["rwkv"] = state["rwkv"].at[:, i].set(0.0)
    for k in ("shift_t", "shift_c"):
        if k in state:
            state[k] = state[k].at[:, i].set(0.0)


class DenseAdapter:
    """Full-batch stepping over ``Model.decode_step`` (any arch family).

    Inactive rows step with token 0 and their results are discarded —
    the legacy ``ServeLoop`` semantics, kept so dense serving stays one
    jitted call per step with a stable trace.
    """

    def __init__(self, model, params) -> None:
        import jax

        self.model = model
        self.params = params
        self._step = jax.jit(model.decode_step)

    def init_state(self, batch_size: int, max_seq: int) -> dict:
        return self.model.init_decode_state(batch_size, max_seq)

    def reset_slot(self, state: dict, i: int) -> None:
        _reset_state_slot(state, i)

    def step(self, state: dict, tokens: np.ndarray,
             active: Sequence[int]) -> tuple[np.ndarray, dict]:
        """tokens: (n_active,) int32 aligned with ``active`` slot ids.
        Returns (logits rows aligned with ``active``, new state)."""
        import jax.numpy as jnp

        with span("repro.adapter.step"):
            b = int(np.asarray(state["pos"]).shape[0])
            toks = np.zeros(b, dtype=np.int32)
            toks[list(active)] = tokens
            logits, state = self._step(self.params, state,
                                       jnp.asarray(toks), None)
            with span("repro.adapter.logits"):
                logits = np.asarray(logits, np.float32)
            return logits[list(active)], state


class PackedAdapter:
    """Ragged-M stepping over a :class:`~repro.tree.PackedTree`.

    Each step runs ``packed_decode_step`` with ``slot_ids`` = the active
    slots only: the batch the matmuls see has M = n_active rows (the
    kernels pad M internally), inactive rows cost nothing, and only
    active rows' clocks advance.  With ``uploader`` set, per-layer
    stream words come through the double-buffered
    :class:`~repro.engine.streams.StreamUploader` instead of resident
    device buffers — the next layer's transfer overlaps this layer's
    matmuls.

    ``kv="packed"`` swaps the dense per-slot K/V caches for a
    :class:`~repro.kvcache.PackedKVCache`: quantized token pages in the
    Iris-planned stream layout, appended through the device pack tables
    and consumed by the stream-direct attention kernel
    (``kv_attention="dense"`` keeps the packed pages but decodes them to
    a dense oracle first — the bit-identity verification path).
    """

    def __init__(self, cfg, tree, *, weights: str = "auto", uploader=None,
                 kv: str = "dense", kv_attention: str = "stream",
                 kv_bits: int | None = None, page_tokens: int = 8,
                 kv_m: int = 512) -> None:
        from repro.models.model import Model

        if kv not in ("dense", "packed"):
            raise ValueError(f"kv must be 'dense' or 'packed', got {kv!r}")
        if kv_attention not in ("stream", "dense"):
            raise ValueError(
                f"kv_attention must be 'stream' or 'dense', "
                f"got {kv_attention!r}")
        self.cfg = cfg
        self.tree = tree
        self.weights = weights
        self.uploader = uploader
        self.kv = kv
        self.kv_attention = kv_attention
        self.kv_bits = kv_bits
        self.page_tokens = page_tokens
        self.kv_m = kv_m
        self._model = Model(cfg, remat="none")

    def init_state(self, batch_size: int, max_seq: int) -> dict:
        state = self._model.init_decode_state(batch_size, max_seq)
        if self.kv == "packed":
            from repro.kvcache import PackedKVCache

            bits = self.kv_bits if self.kv_bits is not None \
                else self.tree.spec.bits
            state["packed_kv"] = PackedKVCache.create(
                self.cfg, bits=bits, page_tokens=self.page_tokens,
                n_slots=batch_size, max_seq=max_seq, m=self.kv_m)
        return state

    def reset_slot(self, state: dict, i: int) -> None:
        _reset_state_slot(state, i)

    def step(self, state: dict, tokens: np.ndarray,
             active: Sequence[int]) -> tuple[np.ndarray, dict]:
        import jax.numpy as jnp

        from repro.models.quantized import packed_decode_step

        with span("repro.adapter.step"):
            logits, state = packed_decode_step(
                self.cfg, self.tree, state, jnp.asarray(tokens, jnp.int32),
                weights=self.weights,
                slot_ids=jnp.asarray(list(active), jnp.int32),
                stream_source=self.uploader,
                kv=self.kv, kv_attention=self.kv_attention)
            with span("repro.adapter.logits"):
                return np.asarray(logits, np.float32), state

    def stream_bytes_uploaded(self) -> int | None:
        """The uploader's lifetime byte count (the engine counts its
        own uploads through :meth:`uploader_stats`)."""
        return self.uploader.bytes_uploaded if self.uploader else None

    def uploader_stats(self) -> dict | None:
        return self.uploader.stats() if self.uploader else None


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------
class Engine:
    """Multi-tenant continuous-batching serving engine.

    Typical use::

        eng = Engine(PackedAdapter(cfg, tree), EngineConfig(4, 128))
        eng.submit(EngineRequest(uid=0, prompt=[1, 2], max_new_tokens=8))
        eng.run_until_drained()
        eng.metrics.snapshot()          # p50/p99 latency, tokens/s, ...
    """

    def __init__(self, adapter, config: EngineConfig, *,
                 sampler: Callable[[Any, EngineRequest], int] = greedy_sampler,
                 queue: AdmissionQueue | None = None,
                 metrics: EngineMetrics | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 hooks: dict[str, list] | None = None) -> None:
        self.adapter = adapter
        self.config = config
        self.sampler = sampler
        self.clock = clock
        self.queue = queue if queue is not None else AdmissionQueue(
            config.max_backlog, clock=clock)
        self.metrics = metrics if metrics is not None \
            else EngineMetrics(clock=clock)
        # a fresh engine starts with a clean host-fallback dedup slate:
        # warnings a previous engine's run already surfaced must fire
        # again for this one, or a long-lived process silently reuses
        # host fallbacks across unrelated serving sessions
        try:
            from repro.kernels import layout_decode, layout_pack
        except ImportError:              # pragma: no cover - needs jax
            pass
        else:
            layout_decode.reset_host_fallback_warnings()
            layout_pack.reset_host_fallback_warnings()
        self.state = adapter.init_state(config.batch_size, config.max_seq)
        self.slots: list[EngineRequest | None] = [None] * config.batch_size
        self.slot_pos = np.zeros(config.batch_size, dtype=np.int64)
        self.hooks: dict[str, list] = {s: [] for s in STAGES}
        for stage, fns in (hooks or {}).items():
            for fn in fns:
                self.add_hook(stage, fn)
        #: engine steps taken (the step number of ``repro.engine.step``)
        self.steps_taken = 0
        self._upload_seen = self._upload_counters()
        # retire-order audit trail (slot-reuse invariants in tests)
        self.admission_order: list[int] = []
        self.completion_order: list[int] = []

    # -- introspection --------------------------------------------------
    def add_hook(self, stage: str,
                 fn: Callable[["Engine", str, dict], None]) -> None:
        """Register ``fn(engine, stage, ctx)`` to run after ``stage``."""
        if stage not in self.hooks:
            raise KeyError(f"unknown stage {stage!r}; stages are {STAGES}")
        self.hooks[stage].append(fn)

    def active_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is not None]

    @property
    def n_active(self) -> int:
        return len(self.active_slots())

    @property
    def stats(self) -> ServeStats:
        """Legacy counter view (``runtime.serve_loop`` compatibility)."""
        m = self.metrics
        return ServeStats(steps=m.steps, tokens_generated=m.tokens_generated,
                          completed=m.completed, admitted=m.admitted)

    # -- request entry --------------------------------------------------
    def submit(self, req: EngineRequest) -> Admission:
        """Admit ``req`` to the backlog (or reject it with a reason)."""
        now = self.clock()
        self.metrics.record_submit(req.uid, now)
        adm = self.queue.submit(req, now)
        if not adm:
            self.metrics.record_reject(req.uid, adm.reason, now)
        return adm

    # -- stages ---------------------------------------------------------
    def _stage_admit(self, ctx: dict) -> None:
        """queue -> free slots, per the admission policy."""
        if self.config.policy == "static" and self.n_active:
            return                      # static batching: drain first
        now = self.clock()
        for i in range(self.config.batch_size):
            if self.slots[i] is not None:
                continue
            rejected0 = len(self.queue.rejections)
            req = self.queue.pop(now)
            # deadline expiries surfaced by pop land in the metrics too
            for uid, reason in self.queue.rejections[rejected0:]:
                self.metrics.record_reject(uid, reason, now)
            if req is None:
                break
            self.slots[i] = req
            self.slot_pos[i] = 0
            req.status = "active"
            self.adapter.reset_slot(self.state, i)
            self.metrics.record_admit(req.uid, now)
            self.admission_order.append(req.uid)
            ctx.setdefault("admitted", []).append((i, req.uid))

    def _stage_prefill(self, ctx: dict) -> None:
        """Assemble the ragged token batch for the active slots: prompt
        token for prompt-phase slots, last sampled token otherwise."""
        active = self.active_slots()
        toks = np.zeros(len(active), dtype=np.int32)
        for j, i in enumerate(active):
            req = self.slots[i]
            p = int(self.slot_pos[i])
            if p < len(req.prompt):
                toks[j] = req.prompt[p]
            elif req.generated:
                toks[j] = req.generated[-1]
        ctx["active"] = active
        ctx["tokens"] = toks

    def _stage_decode(self, ctx: dict) -> None:
        """One adapter step over the active rows (ragged M)."""
        active = ctx["active"]
        if not active:
            ctx["logits"] = np.zeros((0, 0), np.float32)
            return
        logits, self.state = self.adapter.step(self.state, ctx["tokens"],
                                               active)
        ctx["logits"] = logits
        self.metrics.record_step(len(active))
        # the uploader outlives engines: count what it did since this
        # engine's previous decode stage (or its construction)
        now, seen = self._upload_counters(), self._upload_seen
        if now is not None and seen is not None:
            self.metrics.record_uploads(
                {k: now[k] - seen[k] for k in UPLOAD_COUNTERS})
            self._upload_seen = now

    def _upload_counters(self) -> dict | None:
        """The adapter's uploader counters, None without an uploader."""
        stats_fn = getattr(self.adapter, "uploader_stats", None)
        stats = stats_fn() if stats_fn is not None else None
        return None if stats is None else {k: stats[k]
                                           for k in UPLOAD_COUNTERS}

    def _stage_retire(self, ctx: dict) -> None:
        """Per-slot sampling, completion checks, slot release."""
        now = self.clock()
        for j, i in enumerate(ctx["active"]):
            req = self.slots[i]
            self.slot_pos[i] += 1
            p = int(self.slot_pos[i])
            if p < len(req.prompt):
                continue                  # still consuming the prompt
            tok = self.sampler(ctx["logits"][j], req)
            if not req.generated:
                self.metrics.record_first_token(req.uid, now)
            req.generated.append(tok)
            self.metrics.record_token(req.uid)
            eos = self.config.eos_token
            if (len(req.generated) >= req.max_new_tokens
                    or (eos is not None and tok == eos)
                    or p >= self.config.max_seq - 1):
                req.done = True
                req.status = "done"
                self.metrics.record_complete(req.uid, now)
                self.completion_order.append(req.uid)
                self.slots[i] = None
                ctx.setdefault("retired", []).append((i, req.uid))

    # -- driving --------------------------------------------------------
    def step(self) -> dict:
        """Run one admit -> prefill -> decode -> retire cycle; returns
        the step context (admitted/active/tokens/retired)."""
        ctx: dict = {}
        with step_span(self.steps_taken):
            for stage in STAGES:
                with span(f"repro.engine.{stage}"):
                    getattr(self, f"_stage_{stage}")(ctx)
                    for fn in self.hooks[stage]:
                        fn(self, stage, ctx)
        self.steps_taken += 1
        return ctx

    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    def run_until_drained(self, max_steps: int = 10_000) -> ServeStats:
        """Step until queue and slots are empty (or ``max_steps``)."""
        steps0 = self.metrics.steps
        while self.has_work():
            if self.metrics.steps - steps0 >= max_steps:
                break
            self.step()
        return self.stats
