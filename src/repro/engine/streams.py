"""Async double-buffered host->device stream uploads.

The stream-direct serving path reads each layer's packed Iris stream as
a flat uint32 device array (``kernels.stream_matmul``).  When the whole
model does not live on-device — the millions-of-users regime the
ROADMAP targets, where HBM holds a working set and host memory holds the
rest — every decode step must ship the next layer bundle up.  Done
naively that serializes transfer behind compute; the paper's bandwidth
argument (and the HLS dataflow literature it cites) says the stream only
pays off when it stays saturated.

:class:`StreamUploader` keeps it saturated with a classic two-deep
buffer ring:

* buffers are keyed by ``(manifest signature, layer)`` — trees that
  share a :class:`~repro.tree.LayoutManifest` signature share ring
  entries, mirroring how the layout cache dedupes plans;
* fetching layer ``L`` immediately schedules ``jax.device_put`` of
  layer ``L+1`` on a side thread, so the next bundle's transfer overlaps
  the current layer's matmuls;
* the ring holds ``depth`` (default 2) in-flight buffers; older entries
  fall out and their device memory is released — host->device traffic is
  bounded by two layer bundles regardless of model depth.

The uploader is the engine's ``stream_source``: calling it with a layer
index returns that layer's device words
(:func:`repro.models.quantized.packed_decode_step` consumes it directly).
Upload byte/hit counters feed :class:`~repro.engine.metrics.EngineMetrics`.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable

from .trace import span

__all__ = ["BufferRing", "StreamUploader"]


class BufferRing:
    """FIFO ring of at most ``depth`` in-flight keyed buffers.

    Inserting beyond capacity evicts the oldest entry (its device buffer
    is dropped and garbage-collected).  ``get`` does not consume — the
    current layer's buffer stays resident while the next one uploads.
    """

    def __init__(self, depth: int = 2) -> None:
        if depth < 1:
            raise ValueError(f"ring depth must be >= 1, got {depth}")
        self.depth = depth
        self._entries: OrderedDict[Any, Any] = OrderedDict()
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Any) -> bool:
        return key in self._entries

    def get(self, key: Any) -> Any | None:
        return self._entries.get(key)

    def put(self, key: Any, value: Any) -> None:
        if key in self._entries:
            self._entries[key] = value
            self._entries.move_to_end(key)
            return
        self._entries[key] = value
        while len(self._entries) > self.depth:
            self._entries.popitem(last=False)
            self.evictions += 1

    def keys(self) -> list[Any]:
        return list(self._entries)


class StreamUploader:
    """Double-buffered host->device uploader over a ``PackedTree``.

    The tree's per-layer stream buffers stay on host (numpy); device
    copies materialize through the ring on demand.  One worker thread
    owns all ``device_put`` calls — uploads are serialized with each
    other (PCIe-order realistic) but overlap the caller's compute.

    Use as a context manager or call :meth:`close` to stop the worker.
    """

    def __init__(self, tree, *, depth: int = 2,
                 device_put: Callable[[Any], Any] | None = None) -> None:
        if tree.streams is None:
            raise ValueError(
                "tree was built with with_streams=False; stream uploads "
                "need the host stream buffers"
            )
        self.tree = tree
        self.n_layers = tree.manifest.n_layers
        #: ring keys lead with the manifest signature: trees sharing a
        #: layout signature share entries
        self._sig = tree.manifest.signature
        self.ring = BufferRing(depth)
        self._host: dict[int, Any] = {}
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="iris-stream-upload")
        if device_put is None:
            import jax
            device_put = jax.device_put
        self._device_put = device_put
        # counters (consumed by EngineMetrics via the engine)
        self.uploads = 0
        self.bytes_uploaded = 0
        self.prefetch_hits = 0
        self.sync_fetches = 0

    # ------------------------------------------------------------------
    def _host_words(self, layer: int):
        words = self._host.get(layer)
        if words is None:
            words = self.tree.host_stream_words(layer)
            self._host[layer] = words
        return words

    def _upload(self, layer: int):
        with span("repro.stream.upload", layer=layer):
            words = self._host_words(layer)
            out = self._device_put(words)
        with self._lock:
            self.uploads += 1
            self.bytes_uploaded += int(words.nbytes)
        return out

    def prefetch(self, layer: int) -> None:
        """Schedule layer ``layer``'s upload on the worker (idempotent
        while the buffer is still in the ring)."""
        layer = layer % self.n_layers
        key = (self._sig, layer)
        with self._lock:
            if key in self.ring:
                return
            fut = self._pool.submit(self._upload, layer)
            self.ring.put(key, fut)

    def __call__(self, layer: int):
        """Device words for ``layer`` — the engine's ``stream_source``.

        Blocks only if the buffer was never prefetched (cold start /
        ring evicted); before returning, schedules ``layer+1`` so its
        transfer rides under the caller's compute for this layer.
        """
        layer = layer % self.n_layers
        key = (self._sig, layer)
        with self._lock:
            entry = self.ring.get(key)
        if entry is None:
            self.sync_fetches += 1
            with span("repro.stream.wait", layer=layer):
                value = self._upload(layer)
            with self._lock:
                self.ring.put(key, value)
        else:
            if isinstance(entry, Future):
                with span("repro.stream.wait", layer=layer):
                    value = entry.result()
                with self._lock:
                    # cache the resolved array (idempotent re-reads)
                    self.ring.put(key, value)
            else:
                value = entry
            self.prefetch_hits += 1
        if self.n_layers > 1:
            self.prefetch(layer + 1)
        return value

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "uploads": self.uploads,
            "bytes_uploaded": self.bytes_uploaded,
            "prefetch_hits": self.prefetch_hits,
            "sync_fetches": self.sync_fetches,
            "ring_depth": self.ring.depth,
            "ring_evictions": self.ring.evictions,
        }

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    def __enter__(self) -> "StreamUploader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
