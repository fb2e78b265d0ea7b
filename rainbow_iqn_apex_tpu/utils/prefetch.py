"""Learner-side batch prefetch pipeline.

Parity: the reference learner's Redis batch fetch overlaps the GPU step only
by accident of redis-py socket buffering (SURVEY.md §3.1); here the overlap
is explicit — a worker thread samples the replay, assembles the dense batch,
and stages it to the device while the learn step for the previous batch is
still executing.  With JAX's async dispatch the main thread never blocks on
host-side sampling, so the accelerator step time is the loop's floor.

Priority write-back consequently lags by the pipeline depth — exactly the
staleness semantics the distributed reference already has (the learner's
priority updates race later samples through Redis).  The write-back side of
that overlap is the depth-K ring in utils/writeback.py: together they make
the steady-state learn loop issue zero blocking host<->device transfers per
step (docs/PERFORMANCE.md has the sync-point inventory).
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, Optional

import jax


class BatchPrefetcher:
    """Background sampler: fn() -> host batch, staged to device ahead of use.

    The GIL is the synchronisation story, matching the replay's in-process
    single-writer discipline (appends happen on the main thread between
    get() calls; NumPy ops release the GIL only inside C loops that don't
    observe partial Python-level state).

    When an obs MetricRegistry is attached, the pipeline exports its own
    health onto it (role "prefetch"), so obs_report can tell learner
    STARVATION (sampler too slow: queue depth pinned at 0, empty-wait count
    climbing) from device-bound steps (queue full, no empty waits):

      prefetch_queue_depth       gauge: staged batches ready to consume
      prefetch_empty_wait_total  counter: get() calls that found it empty
      prefetch_empty_wait_s     histogram: how long those gets blocked
    """

    def __init__(
        self,
        sample_fn: Callable[[], Any],
        depth: int = 2,
        device_put: bool = True,
        registry=None,
        role: str = "prefetch",
    ):
        self.sample_fn = sample_fn
        self.depth = depth
        self.device_put = device_put
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._exc: Optional[BaseException] = None
        self._g_depth = self._c_empty = self._h_wait = None
        if registry is not None:
            self._g_depth = registry.gauge("prefetch_queue_depth", role)
            self._c_empty = registry.counter("prefetch_empty_wait_total", role)
            self._h_wait = registry.histogram("prefetch_empty_wait_s", role)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self) -> None:
        while not self._stop.is_set():
            try:
                batch = self.sample_fn()
                if self.device_put:
                    batch = jax.tree.map(jax.device_put, batch)
            except BaseException as e:  # surfaced on the consumer thread
                self._exc = e
                self._q.put(None)
                return
            # block while the queue is full (bounded staleness)
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.1)
                    if self._g_depth is not None:
                        self._g_depth.set(self._q.qsize())
                    break
                except queue.Full:
                    continue

    def get(self, timeout: float = 60.0):
        if self._exc is not None and self._q.empty():
            # repeated get() after a surfaced failure: fail fast, don't hang
            raise RuntimeError("prefetch worker failed") from self._exc
        empty_at_get = self._q.empty()
        if empty_at_get and self._c_empty is not None:
            self._c_empty.inc()  # starvation signal: consumer outran sampler
            t0 = time.monotonic()
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"prefetch worker produced nothing for {timeout}s "
                "(replay sampler stalled or device transfer wedged)"
            ) from None
        if self._g_depth is not None:
            self._g_depth.set(self._q.qsize())
            if empty_at_get:
                self._h_wait.observe(time.monotonic() - t0)
        if item is None and self._exc is not None:
            raise RuntimeError("prefetch worker failed") from self._exc
        return item

    def close(self) -> None:
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)


class SampleAheadPusher(BatchPrefetcher):
    """Sample-ahead PUSH pipeline over the device sample frontier
    (replay/frontier.py): the worker consumes device-drawn index blocks,
    assembles frames from host DRAM at those indices, stages them to the
    device, and pushes ready ``(idx, batch)`` pairs into the learner's
    bounded queue — the learner never initiates sampling, it only pops.

    Mechanics per worker turn: keep ``draw_ahead`` index BLOCKS (each
    ``draw_block`` stratified batches in one fused dispatch, which
    amortises the dispatch overhead) in flight on device; materialize the
    oldest block on THIS thread (the guard flags are thread-local, so the
    learner's ``forbid_host_sync()`` region is untouched); then gather one
    batch per turn through ``assemble_fn``.

    Extra gauges on the shared registry (role ``prefetch``; surfaced in
    obs_report's ``pipeline:`` line):

      sample_ahead_queue_depth          staged batches ready to pop
      sample_ahead_stale_indices_total  rows served across a shard
                                        drop/readmit epoch flip (the
                                        accepted sample-ahead staleness,
                                        made visible)

    ``prefetch_queue_depth`` / ``prefetch_empty_wait_*`` stay live through
    the base class, so existing starvation triage keeps working.

    ``reuse`` (cfg.replay_ratio, docs/PERFORMANCE.md "Replay reuse"): one
    staged batch feeds K fused learn passes, so the learner pops K-fold
    fewer batches per learn step — BOTH the staged-queue ``depth`` and the
    device-side ``draw_ahead`` shrink by the same factor (ceil, floor 1)
    HERE, in one place, keeping HBM index blocks and host gather work
    proportional to the SAMPLE rate instead of the step rate.  Callers
    pass their un-shrunk depths plus ``reuse``.
    """

    def __init__(
        self,
        frontier,
        assemble_fn: Callable[[Any, Any], Any],  # (idx, weight) -> item
        batch_size: int,
        beta_fn: Callable[[], float],
        n_items_fn: Callable[[], int],
        depth: int = 2,
        draw_ahead: int = 2,
        reuse: int = 1,
        registry=None,
        role: str = "prefetch",
    ):
        self.frontier = frontier
        self._assemble = assemble_fn
        self._B = int(batch_size)
        self._beta_fn = beta_fn
        self._n_items_fn = n_items_fn
        shrink = max(int(reuse), 1)
        self._draw_ahead = max(-(-int(draw_ahead) // shrink), 1)
        depth = max(-(-int(depth) // shrink), 1)
        self._blocks: collections.deque = collections.deque()
        self._batches: collections.deque = collections.deque()
        self._g_sa_depth = self._c_stale = None
        if registry is not None:
            self._g_sa_depth = registry.gauge("sample_ahead_queue_depth", role)
            self._c_stale = registry.counter(
                "sample_ahead_stale_indices_total", role
            )
        super().__init__(
            self._produce, depth=max(int(depth), 1), device_put=False,
            registry=registry, role=role,
        )

    def _produce(self):
        while len(self._blocks) < self._draw_ahead:
            self._blocks.append(self.frontier.draw(
                self._B, self._beta_fn(), self._n_items_fn()
            ))
        if not self._batches:
            import numpy as np

            block = self._blocks.popleft()
            # worker-thread sync: by now draw_ahead-1 newer blocks are queued
            # behind it on device, so the values are (nearly always) ready
            idx = np.asarray(block.idx)
            weight = np.asarray(block.weight)
            stale = self.frontier.stale_rows(idx, block.stamp)
            if stale and self._c_stale is not None:
                self._c_stale.inc(stale)
            for g in range(block.groups):
                self._batches.append((idx[g].astype(np.int64), weight[g]))
        idx_b, w_b = self._batches.popleft()
        return self._assemble(idx_b, w_b)

    def get(self, timeout: float = 60.0):
        item = super().get(timeout=timeout)
        if self._g_sa_depth is not None:
            self._g_sa_depth.set(self._q.qsize())
        return item


def make_replay_prefetcher(
    memory, cfg, beta_fn: Callable[[], float], registry=None
) -> "BatchPrefetcher":
    """The train-loop wiring, shared by the single-process and apex loops:
    sample -> (idx, device-staged Batch); jnp.asarray inside to_device_batch
    already performs the (async) host->device transfer, so device_put=False.
    """
    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch

    def _sample():
        s = memory.sample(cfg.batch_size, beta_fn())
        return s.idx, to_device_batch(s)

    return BatchPrefetcher(
        _sample, depth=cfg.prefetch_depth, device_put=False, registry=registry
    )
