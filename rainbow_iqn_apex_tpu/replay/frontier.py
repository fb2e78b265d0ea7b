"""Device-resident sample frontier: the Ape-X host replay's priority vector
mirrored into HBM, with one fused XLA draw kernel over it.

Why this exists (ISSUE 6; ROADMAP "in-network experience sampling",
arXiv:2110.13506): PR 5 made the learner's *write-back* side issue zero
blocking transfers per step, but the *sample* side still walked host
sum-trees and assembled batches in NumPy on every step, on one host
thread (the PR 5 prefetch starvation gauges show when the learner outruns
it).  This module moves the DRAW off the host path:

- ``DeviceSampleFrontier`` mirrors every shard's tree-space priority leaves
  into one device vector ``[num_shards * shard_capacity]`` and draws
  stratified proportional index blocks with the same masked-cumsum +
  searchsorted primitive ``replay/device.py`` already proved for Anakin —
  global indices, sample probabilities and max-normalised IS weights all
  computed on device, ``G`` index-batches per dispatch so the per-batch
  dispatch overhead amortises away.
- Learner priority write-back retires **directly into the mirror** as a
  jitted scatter of the ring's still-on-device ``|TD|`` array
  (``utils/writeback.py`` with ``materialize_priorities=False``) — the
  host sum-tree becomes a *cold-path* source of truth (snapshot/restore,
  readmission re-seed), reconciled from the mirror at ring-drain
  boundaries (``reconcile``).
- Host appends keep writing the host tree as before; each append's three
  disjoint leaf updates (fresh slot, cursor dead zone, ready slot) are
  *staged* as (slot, value) deltas and flushed to the mirror as one
  batched scatter — an async host→device copy of a few dozen floats per
  tick, never a sync.

Sampling DISTRIBUTION parity with the host path: the host draws a
multinomial shard split then stratifies per shard; the frontier stratifies
once over the global vector.  Both sample slot *i* with probability
``p_i / sum(p)`` (tests/test_device_sampling.py chi-squares both against
the exact distribution), and the IS weights use the identical
``(N * P(i))^-beta / max`` formula at fp32 (the same precision trade
replay/device.py documents for the Anakin cumsum).

Fencing (PR 2/4 invariants): ``on_drop`` zeroes the dead shard's mirror
slice, so draws exclude it and the never-resurrect rule (a write-back
lands only where the mirror is already > 0) drops any in-flight lagged
write-back to it on the floor; ``on_readmit`` refreshes the slice from the
host tree under the NEW epoch, so a zombie incarnation's staleness can
never leak through the mirror.  Draw blocks carry an epoch/dead-set stamp;
the sample-ahead pusher (utils/prefetch.py) counts rows served across an
epoch flip as ``sample_ahead_stale_indices_total``.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from rainbow_iqn_apex_tpu.utils import hostsync


class DrawBlock:
    """One dispatched draw: ``G`` stratified index-batches still on device,
    plus the epoch/dead-set stamp the mirror had when it was drawn."""

    __slots__ = ("idx", "weight", "prob", "stamp", "group_size", "groups")

    def __init__(self, idx, weight, prob, stamp, group_size: int, groups: int):
        self.idx = idx  # [G, B] int32 global slot ids (device)
        self.weight = weight  # [G, B] f32 per-batch max-normalised IS (device)
        self.prob = prob  # [G, B] f32 global sample probability (device)
        self.stamp = stamp  # (epochs tuple, dead frozenset) at draw time
        self.group_size = group_size
        self.groups = groups


class DeviceSampleFrontier:
    """HBM priority mirror + fused stratified draw + in-mirror write-back.

    Built over a list of host ``SumTree``s (one per replay shard, all of
    capacity ``shard_capacity``); ``from_sharded`` / ``from_sequence`` wire
    the two replay flavours.  All mirror mutation (write-back scatters,
    staged-append flushes, drop/readmit slice edits) is serialized by one
    lock — dispatches are async, so the critical sections are microseconds
    and the learner/pusher threads never wait on device completion here.
    """

    def __init__(
        self,
        trees: Sequence,  # SumTree per shard (host truth, cold path)
        shard_capacity: int,
        eps: float,
        omega: float,
        registry=None,
        role: str = "frontier",
        seed: int = 0,
        draw_block: int = 8,
        reseed_max_priority: Optional[Callable[[int, float], None]] = None,
    ):
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self.trees = list(trees)
        self.cap = int(shard_capacity)
        self.size = len(self.trees) * self.cap
        if self.size >= np.iinfo(np.int32).max:
            raise ValueError("mirror too large for int32 slot ids")
        self.eps = float(eps)
        self.omega = float(omega)
        self.draw_block = max(int(draw_block), 1)
        self._reseed = reseed_max_priority
        self._lock = threading.Lock()
        self._pending: List[Tuple[np.ndarray, np.ndarray]] = []
        self._pending_rows = 0
        self._epochs = [0] * len(self.trees)
        self._dead: set = set()
        self._all_local = np.arange(self.cap, dtype=np.int64)
        self.reconciles = 0
        self._g_reconcile = None
        if registry is not None:
            self._g_reconcile = registry.gauge("mirror_reconcile_s", role)

        N = self.size

        def _draw(mirror, key, beta, n_items, B, G):
            key, sub = jax.random.split(key)
            total = mirror.sum()
            cdf = jnp.cumsum(mirror)
            u = jax.random.uniform(sub, (G, B))
            u = (jnp.arange(B, dtype=jnp.float32)[None, :] + u) / B * total
            idx = jnp.clip(
                jnp.searchsorted(cdf, u.reshape(-1), side="right"), 0, N - 1
            ).astype(jnp.int32).reshape(G, B)
            prob = jnp.maximum(
                mirror[idx] / jnp.maximum(total, 1e-12), 1e-12
            )
            w = (jnp.maximum(n_items, 1.0) * prob) ** (-beta)
            # per-batch max-normalisation: each [B] batch is one learner
            # step, exactly the host formula
            w = (w / w.max(axis=1, keepdims=True)).astype(jnp.float32)
            return key, idx, w, prob

        self._draw_fn = jax.jit(_draw, static_argnames=("B", "G"))

        def _writeback(mirror, idx, td_abs):
            pri = (jnp.abs(td_abs).astype(jnp.float32) + self.eps) ** self.omega
            cur = mirror[idx]
            # never-resurrect: cursor-invalidated AND dead-shard slots stay 0
            # — this is the epoch fence for lagged in-flight write-backs
            pri = jnp.where(cur > 0, pri, 0.0)
            return mirror.at[idx].set(pri)

        self._writeback_fn = jax.jit(_writeback)
        self._scatter_fn = jax.jit(lambda m, i, v: m.at[i].set(v))
        self._slice_fn = jax.jit(
            lambda m, start, vals: jax.lax.dynamic_update_slice(m, vals, (start,))
        )
        self._key = jax.random.PRNGKey(seed)
        self.mirror = jnp.asarray(self._host_leaves())

    # ------------------------------------------------------------ constructors
    @classmethod
    def from_sharded(cls, memory, registry=None, seed: int = 0,
                     draw_block: int = 8) -> "DeviceSampleFrontier":
        """Frontier over a ``parallel.sharded_replay.ShardedReplay``: one
        mirror slice per shard, attached so appends stage deltas and
        drop/readmit fence the mirror (``memory.attach_frontier``)."""
        s0 = memory.shards[0]

        def reseed(k: int, _leaf_max: float) -> None:
            # fresh-item default priority: max over WRITTEN leaves only (the
            # clamped max_leaf — never-written residue must not inflate it)
            shard = memory.shards[k]
            shard.max_priority = max(
                shard.max_priority,
                shard.tree.max_leaf(shard.filled, shard.lanes),
            )

        frontier = cls(
            [s.tree for s in memory.shards],
            memory.shard_capacity,
            eps=s0.eps,
            omega=s0.omega,
            registry=registry,
            seed=seed,
            draw_block=draw_block,
            reseed_max_priority=reseed,
        )
        for k in memory.dead_shards:  # mirror starts fenced like the host
            frontier.on_drop(k)
        memory.attach_frontier(frontier)
        return frontier

    @classmethod
    def from_sequence(cls, memory, registry=None, seed: int = 0,
                      draw_block: int = 8) -> "DeviceSampleFrontier":
        """Frontier over a single ``replay.sequence.SequenceReplay`` (the
        R2D2 path): one tree, no shard epochs."""

        def reseed(_k: int, _leaf_max: float) -> None:
            memory.max_priority = max(
                memory.max_priority, memory.tree.max_leaf(memory.filled)
            )

        frontier = cls(
            [memory.tree],
            memory.capacity,
            eps=memory.eps,
            omega=memory.omega,
            registry=registry,
            seed=seed,
            draw_block=draw_block,
            reseed_max_priority=reseed,
        )
        memory.attach_frontier(frontier)
        return frontier

    # ---------------------------------------------------------------- helpers
    def _host_leaves(self) -> np.ndarray:
        """Current host-tree leaves as one f32 vector (dead shards zeroed —
        the host tree keeps their mass for readmission, the mirror must
        not sample it)."""
        out = np.empty(self.size, np.float32)
        for k, tree in enumerate(self.trees):
            sl = out[k * self.cap:(k + 1) * self.cap]
            if k in self._dead:
                sl[:] = 0.0
            else:
                sl[:] = tree.tree[tree.span:tree.span + self.cap]
        return out

    @property
    def stamp(self) -> Tuple[tuple, frozenset]:
        return (tuple(self._epochs), frozenset(self._dead))

    def stale_rows(self, idx: np.ndarray, stamp) -> int:
        """How many of ``idx`` point into shards whose epoch flipped (drop
        or readmit) since ``stamp`` was taken — the rows a sample-ahead
        batch served past a fence event."""
        epochs, dead = stamp
        changed = [
            k for k in range(len(self.trees))
            if self._epochs[k] != epochs[k] or (k in self._dead) != (k in dead)
        ]
        if not changed:
            return 0
        shard_of = np.asarray(idx).ravel() // self.cap
        # materializing drawn indices at gather time is the design (PR 6):
        # host-sync-ok: runs on the pusher worker thread, not the learner
        return int(np.isin(shard_of, changed).sum())

    # ------------------------------------------------------------------ draw
    def draw(self, batch_size: int, beta: float, n_items: int,
             groups: Optional[int] = None) -> DrawBlock:
        """Dispatch one fused draw of ``groups`` stratified index-batches
        (async — nothing blocks here).  Each [B] row is one learner batch:
        stratified over the global mass exactly like the host's
        multinomial-split + per-shard strata, with its own max-normalised
        IS weights."""
        G = self.draw_block if groups is None else max(int(groups), 1)
        self.flush_staged()
        with self._lock:
            self._key, idx, w, prob = self._draw_fn(
                self.mirror, self._key, float(beta), float(max(n_items, 1)),
                B=int(batch_size), G=G,
            )
            stamp = self.stamp
        return DrawBlock(idx, w, prob, stamp, int(batch_size), G)

    # ------------------------------------------------------------- write-back
    def update(self, idx, td_abs) -> None:
        """Learner priority write-back straight into the mirror (the
        ``RingCommitter`` update target when device sampling is on).  Both
        arguments may still be device arrays — this is a dispatch, not a
        sync.  Duplicate slots within one batch land in unspecified order
        (the host tree keeps the last; PER is insensitive to which of two
        same-step |TD| rows wins).  Staged append deltas flush FIRST so the
        mirror sees them in program order — otherwise a slot the cursor
        just made eligible would drop this write-back on the
        never-resurrect floor while the host tree kept it."""
        self.flush_staged()
        jnp = self._jnp
        with self._lock:
            self.mirror = self._writeback_fn(
                self.mirror, jnp.asarray(idx), jnp.asarray(td_abs)
            )

    # ------------------------------------------------------- append mirroring
    def stage(self, global_idx: np.ndarray, values: np.ndarray) -> None:
        """Queue host-append leaf deltas (tree-space values at global slot
        ids) for the next flush.  Called from the replay's append path on
        the main thread; flushing happens on the pusher thread before each
        draw (or inline past a size threshold, still just an async
        dispatch)."""
        with self._lock:
            self._pending.append((
                np.asarray(global_idx, np.int64).ravel(),
                np.asarray(values, np.float32).ravel(),
            ))
            self._pending_rows += len(self._pending[-1][0])
            flush_now = self._pending_rows >= 4096
        if flush_now:
            self.flush_staged()

    def flush_staged(self) -> None:
        """Apply every staged append delta as one batched scatter (last
        write per slot wins, matching the host tree's sequential order)."""
        with self._lock:
            if not self._pending:
                return
            pending, self._pending, self._pending_rows = self._pending, [], 0
            idx = np.concatenate([i for i, _ in pending])
            vals = np.concatenate([v for _, v in pending])
            if idx.size > 1:  # keep the LAST write per duplicate slot
                _, last_pos = np.unique(idx[::-1], return_index=True)
                keep = idx.size - 1 - last_pos
                idx, vals = idx[keep], vals[keep]
            # dead shards stay fenced: their staged rows (an append racing
            # the drop) must not repopulate the zeroed slice
            if self._dead:
                alive = ~np.isin(idx // self.cap, sorted(self._dead))
                idx, vals = idx[alive], vals[alive]
            if idx.size:
                self.mirror = self._scatter_fn(
                    self.mirror, idx.astype(np.int32), vals
                )

    # -------------------------------------------------------------- elasticity
    def on_drop(self, k: int) -> None:
        """Shard ``k`` died: zero its mirror slice so draws exclude it and
        lagged write-backs to it can never resurrect (the mirror-side twin
        of ``ShardedReplay.drop_shard``)."""
        jnp = self._jnp
        with self._lock:
            self._dead.add(k)
            self._epochs[k] += 1
            self.mirror = self._slice_fn(
                self.mirror, k * self.cap, jnp.zeros((self.cap,), jnp.float32)
            )

    def on_readmit(self, k: int) -> None:
        """Shard ``k`` rejoined under a new lease epoch: refresh its slice
        from the host tree (the cold-path source of truth the rejoining
        host restored or re-seeded)."""
        jnp = self._jnp
        tree = self.trees[k]
        # host-sync-ok: host sum-tree slice on the cold readmission path
        vals = np.asarray(
            tree.tree[tree.span:tree.span + self.cap], np.float32
        )
        with self._lock:
            self._dead.discard(k)
            self._epochs[k] += 1
            self.mirror = self._slice_fn(
                self.mirror, k * self.cap, jnp.asarray(vals)
            )

    def refresh_from_host(self, dead=None) -> None:
        """Reload the whole mirror from the host trees (snapshot restore —
        the cold path rewrote the truth wholesale), optionally adopting the
        owner's restored dead-shard set.  Bumps every shard's frontier
        epoch so in-flight draw blocks read as stale."""
        with self._lock:
            if dead is not None:
                self._dead = set(dead)
            self._pending, self._pending_rows = [], 0
            self._epochs = [e + 1 for e in self._epochs]
            self.mirror = self._jnp.asarray(self._host_leaves())

    # --------------------------------------------------------------- reconcile
    def reconcile(self) -> float:
        """Drain-boundary sync of the COLD path: materialize the mirror
        (sanctioned — drains are already host-device sync points) and write
        it back into the host sum-trees, so snapshots, readmission
        re-seeds, and a later ``device_sampling=off`` run all see the
        learner's priorities.  Returns (and gauges) the wall seconds."""
        t0 = time.perf_counter()
        self.flush_staged()
        with self._lock:
            mirror = self.mirror
        with hostsync.sanctioned():
            host = np.maximum(np.asarray(mirror), 0.0).astype(np.float64)
        for k, tree in enumerate(self.trees):
            if k in self._dead:
                continue  # host tree keeps the dead shard's cold truth
            sl = host[k * self.cap:(k + 1) * self.cap]
            tree.set(self._all_local, sl)
            if self._reseed is not None and sl.size:
                self._reseed(k, float(sl.max()))
        dt = time.perf_counter() - t0
        self.reconciles += 1
        if self._g_reconcile is not None:
            self._g_reconcile.set(dt)
        return dt

    # -------------------------------------------------------------------- test
    def mirror_np(self) -> np.ndarray:
        """Materialize the mirror on host (tests / cold paths only)."""
        with self._lock:
            mirror = self.mirror
        with hostsync.sanctioned():
            return np.asarray(mirror)


def make_batch_assembler(memory, to_device: Callable[[Any], Any],
                         registry=None, role: str = "prefetch"):
    """The pusher's host half for a ShardedReplay: global idx + device
    weights -> staged device Batch (an index-driven frame gather).

    Gather-time cursor fence: indices were drawn against a mirror snapshot,
    and by gather time the ring cursor may have advanced INTO a drawn
    slot's history/n-step window (the lap-straddle race sample-ahead
    opens; the host path closes it by assembling atomically at sample
    time).  The append path keeps every such slot's host-tree leaf at
    zero, so ``eligible_mask`` identifies the invalidated rows exactly —
    their IS weight is zeroed (a zero-weight row contributes nothing to
    the loss, and the never-resurrect rule already drops its priority
    write-back) and they count into ``sample_ahead_stale_indices_total``.
    """
    c_stale = None
    if registry is not None:
        c_stale = registry.counter("sample_ahead_stale_indices_total", role)

    def assemble(idx: np.ndarray, weight: np.ndarray):
        ok = memory.eligible_mask(idx)
        if not ok.all():
            if c_stale is not None:
                # host-sync-ok: host eligible_mask ndarray, pusher thread
                c_stale.inc(int((~ok).sum()))
            weight = np.where(ok, weight, 0.0).astype(np.float32)
        sample = memory.assemble_global(idx, weight)
        # sample.idx, not idx: assemble_global returns rows slot-sorted, and
        # the ring's priority write-back must stay row-aligned with them
        return sample.idx, to_device(sample)

    return assemble
