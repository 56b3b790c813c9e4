"""Async double-buffered chunk staging for the tiled engine (DESIGN.md §11).

The tiled engine streams entry-chunk groups host→device: assemble a
``(S_pad, G, b)`` v-slab on the host, move it to device, run the tile
kernel. Done synchronously, the kernel idles for the full staging time of
every group. ``ChunkPrefetcher`` runs the staging on a producer thread a
configurable ``depth`` of groups ahead (modeled on
``repro.data.tokens.Prefetcher``), so group G+1's host copy and transfer
hide behind group G's compute.

Telemetry (all wall seconds, accumulated across the pass):

  * ``staging_s``   — time the producer spent assembling + transferring;
  * ``stage_wait_s``— time the CONSUMER blocked waiting for a staged group
    (pipeline stall: staging is the bottleneck);
  * ``compute_wait_s`` — time the PRODUCER blocked on a full queue
    (compute is the bottleneck — the healthy state).

``depth=0`` degrades to fully synchronous staging in the consumer's
thread; ``stage_wait_s`` then equals ``staging_s`` by construction, which
is what makes "prefetch hides staging" a measurable claim
(``stage_wait_s`` with prefetch < ``staging_s`` without).

Each staging is a span ``engine.scan.stage`` (on the stage thread, its
parent the span open where the prefetcher was made) and each consumer
wait a span ``engine.scan.wait`` (``repro.utils.trace``); the telemetry
above sums the same clock reads.

A raising stage function surfaces as a typed ``PipelineStageError`` on the
consumer side (original exception chained); ``close`` always reaps the
thread and drains staged payloads so no device buffers are stranded.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable

from repro.utils import trace

#: Sentinel kinds flowing through the queue alongside staged payloads.
_ITEM, _DONE, _ERROR = "item", "done", "error"


class PipelineStageError(RuntimeError):
    """A prefetch stage thread failed; the original exception is chained."""


class ChunkPrefetcher:
    """Iterate staged payloads, staging up to ``depth`` groups ahead.

    ``stage_fn(descriptor)`` runs on the producer thread (``depth`` ≥ 1) or
    inline (``depth=0``) and returns the staged payload. The iterator
    yields payloads in descriptor order and raises ``PipelineStageError``
    if a stage failed. Always ``close()`` in a finally block.
    """

    def __init__(self, descriptors: Iterable, stage_fn: Callable,
                 depth: int = 2):
        """Start staging ``descriptors`` through ``stage_fn``."""
        self.stage_wait_s = 0.0
        self.compute_wait_s = 0.0
        self.staging_s = 0.0
        self._stage_fn = stage_fn
        self._parent = trace.current()
        self._depth = max(int(depth), 0)
        self._stop = False
        self.thread = None
        if self._depth == 0:
            self._it = iter(descriptors)
            return
        self._descs = list(descriptors)
        self.q: queue.Queue = queue.Queue(maxsize=self._depth)
        self.thread = threading.Thread(target=self._work, daemon=True)
        self.thread.start()

    # -- producer ------------------------------------------------------------

    def _put(self, payload) -> bool:
        """Queue-put that never blocks past a ``close()``; False = stopped."""
        while not self._stop:
            try:
                self.q.put(payload, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _work(self) -> None:
        try:
            for d in self._descs:
                if self._stop:
                    return
                with trace.span("engine.scan.stage",
                                parent=self._parent) as sp:
                    staged = self._stage_fn(d)
                self.staging_s += sp.seconds
                ok = self._put((_ITEM, staged))
                self.compute_wait_s += time.perf_counter() - sp.t1
                if not ok:
                    return
            self._put((_DONE, None))
        except BaseException as exc:  # surfaced typed on the consumer side
            self._put((_ERROR, exc))

    # -- consumer ------------------------------------------------------------

    def __iter__(self):
        """Iterator protocol — the engine's group loop is a plain for."""
        return self

    def __next__(self):
        """Next staged payload; blocks until staged (timed as stall)."""
        if self._depth == 0:
            d = next(self._it)           # StopIteration ends the loop
            try:
                with trace.span("engine.scan.stage",
                                parent=self._parent) as sp:
                    staged = self._stage_fn(d)
            except StopIteration:
                raise
            except BaseException as exc:
                raise PipelineStageError(
                    f"chunk staging failed: {exc!r}") from exc
            self.staging_s += sp.seconds
            self.stage_wait_s += sp.seconds   # consumer waited the full time
            return staged
        with trace.span("engine.scan.wait") as sp:
            while True:
                try:
                    kind, payload = self.q.get(timeout=0.5)
                    break
                except queue.Empty:
                    if not self.thread.is_alive():
                        raise PipelineStageError(
                            "prefetch stage thread died without a result")
        self.stage_wait_s += sp.seconds
        if kind == _DONE:
            raise StopIteration
        if kind == _ERROR:
            raise PipelineStageError(
                f"chunk staging failed: {payload!r}") from payload
        return payload

    def close(self) -> None:
        """Stop the stage thread and drop staged payloads (device buffers).

        Idempotent; safe mid-iteration (the engine calls it in a finally on
        success AND failure paths). Draining the queue releases every
        already-staged device array so an aborted pass strands nothing.
        """
        self._stop = True
        if self.thread is None:
            return
        for _ in range(2):               # drain → join → drain again
            try:
                while True:
                    self.q.get_nowait()
            except queue.Empty:
                pass
            if self.thread.is_alive():
                self.thread.join(timeout=5.0)


__all__ = ["ChunkPrefetcher", "PipelineStageError"]
