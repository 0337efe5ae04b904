"""Traffic from a mix file and a seed, and the closed-loop client driver.

A mix (``traffic/<mix>.json``) gives the number of clients and the
distributions of prompt and output lengths.  Requests come in *waves*:
client ``c``'s ``k``-th request belongs to wave ``k``.  Every wave holds
the same stratified sample of each length distribution (the
``clients`` quantiles at ``(j + 0.5) / clients``); the seed only deals
them out to clients, in another order per wave, and draws the token ids
(uniform over the vocabulary).  So every seed offers the same work, and
seeds differ in which client gets which request.

:class:`ClosedLoop` submits one request per client at the window's
start and, after each engine step, the next request of every client
whose request completed, before the next admission: with as many
clients as slots, every slot stays busy and the step's batch never
changes.  It timestamps submissions and every sampled token on the
host clock.
"""
from __future__ import annotations

import math
import time
from typing import Callable

import numpy as np


def stratified(dist: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles ``(j + 0.5) / n`` of
    ``dist`` (``log_uniform`` or ``uniform`` over ``[min, max]``)."""
    lo, hi = int(dist["min"]), int(dist["max"])
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "log_uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    elif dist["dist"] == "uniform":
        x = lo + u * (hi + 1 - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


class Requests:
    """The requests of one run: ``next(client)`` gives that client's next
    (prompt token ids, max new tokens)."""

    def __init__(self, mix: dict, seed: int, vocab: int) -> None:
        if mix["loop"] != "closed":
            raise ValueError(f"loop {mix['loop']!r} is not supported")
        if mix.get("token_ids", "uniform") != "uniform":
            raise ValueError(f"token ids {mix['token_ids']!r} unsupported")
        self.clients = int(mix["clients"])
        self.vocab = vocab
        self._prompt = stratified(mix["prompt_tokens"], self.clients)
        self._output = stratified(mix["output_tokens"], self.clients)
        self._rng = np.random.default_rng(seed)
        self._waves: list[tuple[np.ndarray, np.ndarray]] = []
        self._count = [0] * self.clients

    def _wave(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        while len(self._waves) <= k:
            self._waves.append(
                (self._prompt[self._rng.permutation(self.clients)],
                 self._output[self._rng.permutation(self.clients)]))
        return self._waves[k]

    def next(self, client: int) -> tuple[list[int], int]:
        k = self._count[client]
        self._count[client] += 1
        prompts, outputs = self._wave(k)
        ids = self._rng.integers(1, self.vocab, int(prompts[client]))
        return ids.tolist(), int(outputs[client])


class Record:
    """One request's timeline on the host clock."""

    __slots__ = ("uid", "client", "req", "submitted", "token_times")

    def __init__(self, uid: int, client: int, req, submitted: float):
        self.uid = uid
        self.client = client
        self.req = req
        self.submitted = submitted
        self.token_times: list[float] = []


class ClosedLoop:
    """Drives ``engine`` (a :class:`repro.engine.Engine`) with one
    outstanding request per client."""

    def __init__(self, engine, requests: Requests, make_request: Callable,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self.engine = engine
        self.requests = requests
        self.make_request = make_request
        self.clock = clock
        self.records: list[Record] = []
        self._open: dict[int, Record] = {}
        self.rejected = 0
        #: host time of the latest retire hook: requests submitted then
        #: were submitted as the window closed
        self.last_hook = float("inf")
        engine.add_hook("retire", self._after_retire)

    def _submit(self, client: int, now: float) -> None:
        prompt, max_new = self.requests.next(client)
        uid = len(self.records)
        req = self.make_request(uid=uid, prompt=prompt,
                                max_new_tokens=max_new)
        rec = Record(uid, client, req, now)
        self.records.append(rec)
        self._open[uid] = rec
        if not self.engine.submit(req):
            self.rejected += 1

    def start(self) -> None:
        now = self.clock()
        for c in range(self.requests.clients):
            self._submit(c, now)

    def _after_retire(self, engine, stage, ctx) -> None:
        now = self.clock()
        self.last_hook = now
        for rec in list(self._open.values()):
            if len(rec.req.generated) > len(rec.token_times):
                rec.token_times.extend(
                    [now] * (len(rec.req.generated) - len(rec.token_times)))
        for _slot, uid in ctx.get("retired", []):
            rec = self._open.pop(uid)
            self._submit(rec.client, now)
