"""Zero-sync learner hot path (utils/writeback.py + utils/hostsync.py).

Four properties of the pipelined priority write-back ring:

1. mechanics — depth-K holds exactly K steps in flight, retires oldest-first
   with lag exactly K, depth-0 degenerates to the seed's synchronous loop;
2. static sync guard — the steady-state learn loop issues no blocking
   device->host scalar materialization per step (the regression that
   re-serializes the pipeline), proven by running the REAL train loop under
   ``hostsync.forbid_host_sync()``;
3. determinism — depth-K and depth-0 produce bitwise-identical TrainState
   trajectories at fixed seeds, with priorities written back lagged by
   exactly K (the ring changes WHEN priorities land, never the math);
4. rollback — a NaN-poisoned step detected at the ring boundary quarantines
   EVERY in-flight step's sampled idx set, not just the tripped one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
from rainbow_iqn_apex_tpu.ops.learn import Batch, build_learn_step, init_train_state
from rainbow_iqn_apex_tpu.parallel.supervisor import TrainSupervisor
from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay
from rainbow_iqn_apex_tpu.utils import faults, hostsync
from rainbow_iqn_apex_tpu.utils.prefetch import BatchPrefetcher
from rainbow_iqn_apex_tpu.utils.writeback import RingCommitter, WritebackRing

CFG = Config(
    compute_dtype="float32",
    frame_height=44,
    frame_width=44,
    history_length=2,
    hidden_size=64,
    num_cosines=16,
    num_tau_samples=8,
    num_tau_prime_samples=8,
    num_quantile_samples=4,
    batch_size=16,
)
A = 3


def _fake_info(i, finite=True):
    return {
        "loss": float(i),
        "grad_norm": 1.0,
        "q_mean": 0.5,
        "priorities": np.full(4, float(i)),
        "finite": finite,
    }


# ----------------------------------------------------------------- mechanics
def test_ring_depth_k_lag_and_drain():
    ring = WritebackRing(3)
    retired = []
    for i in range(1, 11):
        r = ring.push(i, np.arange(4) + i, _fake_info(i))
        if i <= 3:
            assert r is None  # filling the ring: nothing retires yet
        else:
            retired.append(r)
            assert r.step == i - 3  # oldest-first, lag EXACTLY depth
            assert r.lag == 3
            assert r.finite and r.scalars["loss"] == float(r.step)
            np.testing.assert_array_equal(r.priorities, np.full(4, float(r.step)))
    assert len(ring) == 3
    tail = ring.drain()
    assert [r.step for r in tail] == [8, 9, 10]
    assert len(ring) == 0
    assert ring.retired_total == 10


def test_ring_depth0_retires_immediately():
    ring = WritebackRing(0)
    r = ring.push(1, np.arange(4), _fake_info(1))
    assert r is not None and r.step == 1 and r.lag == 0
    assert len(ring) == 0


def test_ring_flush_never_materializes_poisoned_infos():
    class Poison:
        """Stands in for a device array whose materialization must not
        happen on the quarantine path."""

        def __array__(self, *a, **k):
            raise AssertionError("flush materialized a poisoned info")

    ring = WritebackRing(2)
    ring.push(1, np.arange(4), {"priorities": Poison(), "finite": True})
    ring.push(2, np.arange(4) + 10, {"priorities": Poison(), "finite": True})
    flushed = ring.flush()
    assert [s for s, _ in flushed] == [1, 2]
    np.testing.assert_array_equal(flushed[1][1], np.arange(4) + 10)
    assert len(ring) == 0


def test_ring_gauges_on_registry():
    reg = MetricRegistry()
    ring = WritebackRing(2, registry=reg, role="learner")
    ring.push(1, np.arange(2), _fake_info(1))
    assert reg.gauge("writeback_inflight", "learner").get() == 1
    ring.push(2, np.arange(2), _fake_info(2))
    ring.push(3, np.arange(2), _fake_info(3))  # retires step 1
    assert reg.gauge("writeback_inflight", "learner").get() == 2
    assert reg.gauge("writeback_lag_steps", "learner").get() == 2


# ---------------------------------------------------------------- sync guard
def test_forbid_host_sync_catches_scalar_materialization():
    """The guard's teeth: float()/int() on a jax array inside the forbidden
    region raises; the same call under sanctioned() (the ring's retirement
    path) passes; other threads are unaffected."""
    x = jax.jit(lambda v: v.sum())(jnp.arange(4.0))
    with hostsync.forbid_host_sync():
        with pytest.raises(hostsync.HostSyncError):
            float(x)
        with pytest.raises(hostsync.HostSyncError):
            hostsync.scalar(x)
        with pytest.raises(hostsync.HostSyncError):
            hostsync.to_host(x)
        with hostsync.sanctioned():
            assert float(x) == 6.0  # the sanctioned sync still works
    assert float(x) == 6.0  # guard removed cleanly


def test_forbid_host_sync_is_thread_local():
    import threading

    x = jax.jit(lambda v: v.sum())(jnp.arange(3.0))
    got = {}

    def other_thread():
        got["value"] = float(x)  # no forbid flag on THIS thread

    with hostsync.forbid_host_sync():
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert got["value"] == 3.0


def test_train_loop_hot_path_issues_no_blocking_sync(tmp_path):
    """THE tentpole guard: the real single-process train loop — prefetcher,
    write-back ring, supervisor, metric cadence — runs end to end inside
    ``forbid_host_sync()``.  Any reintroduced per-step ``float(loss)`` /
    ``int(state.step)`` (the seed's sync points) fails this test; sanctioned
    syncs (ring retirement, snapshot capture at cadence) are the only
    blocking reads allowed.  CPU caveat: plain np.asarray of a CPU-backed
    jax array is below any Python hook, so array-copy regressions are
    covered by the lag-determinism test instead."""
    from rainbow_iqn_apex_tpu.train import train

    cfg = Config(
        env_id="toy:catch",
        compute_dtype="float32",
        frame_height=80,
        frame_width=80,
        history_length=2,
        hidden_size=64,
        num_cosines=16,
        num_tau_samples=8,
        num_tau_prime_samples=8,
        num_quantile_samples=4,
        batch_size=16,
        learning_rate=1e-3,
        multi_step=3,
        gamma=0.9,
        memory_capacity=2048,
        learn_start=128,
        frames_per_learn=2,
        target_update_period=100,
        num_envs_per_actor=4,
        metrics_interval=20,
        eval_interval=0,
        checkpoint_interval=0,
        eval_episodes=2,
        stall_timeout_s=0.0,
        writeback_depth=2,
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"),
        seed=7,
    )
    with hostsync.forbid_host_sync():
        summary = train(cfg, max_frames=500)
    assert summary["learn_steps"] > 0
    assert np.isfinite(summary["eval_score_mean"])


# -------------------------------------------------------------- determinism
def _toy_batches(n, key):
    rng = np.random.default_rng(3)
    out = []
    for _ in range(n):
        out.append(
            Batch(
                obs=jnp.asarray(
                    rng.integers(0, 255, (16, 44, 44, 2), dtype=np.uint8)
                ),
                action=jnp.asarray(rng.integers(0, A, 16).astype(np.int32)),
                reward=jnp.asarray(rng.normal(size=16).astype(np.float32)),
                next_obs=jnp.asarray(
                    rng.integers(0, 255, (16, 44, 44, 2), dtype=np.uint8)
                ),
                discount=jnp.asarray(np.full(16, 0.9, np.float32)),
                weight=jnp.asarray(np.ones(16, np.float32)),
            )
        )
    return out


def test_depth_k_trajectory_bitwise_identical_priorities_lagged():
    """Acceptance: depth-K vs depth-0 TrainState trajectories are bitwise
    identical on params/opt_state at fixed seeds; the priority write-back
    STREAM is identical too, just lagged by exactly K pushes."""
    learn = jax.jit(build_learn_step(CFG, A))  # no donation: states replayed
    batches = _toy_batches(8, None)
    base_key = jax.random.PRNGKey(11)

    def trajectory(depth):
        state = init_train_state(CFG, A, jax.random.PRNGKey(0))
        ring = WritebackRing(depth)
        writes = []  # (push_index, retired_step, priorities)
        losses = []
        for i in range(1, 9):
            state, info = learn(state, batches[i - 1], jax.random.fold_in(base_key, i))
            r = ring.push(i, np.arange(16), info)
            if r is not None:
                writes.append((i, r.step, r.priorities))
                losses.append(r.scalars["loss"])
        for r in ring.drain():
            writes.append((None, r.step, r.priorities))
            losses.append(r.scalars["loss"])
        return state, writes, losses

    s0, w0, l0 = trajectory(0)
    s3, w3, l3 = trajectory(3)

    # bitwise-identical params + opt_state (the ring never touches the math)
    for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s3.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s0.opt_state), jax.tree.leaves(s3.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # same write-back stream content, ordered by step, values bitwise equal
    assert [s for _, s, _ in w0] == list(range(1, 9))
    assert [s for _, s, _ in w3] == list(range(1, 9))
    for (_, s_a, p_a), (_, s_b, p_b) in zip(w0, w3):
        assert s_a == s_b
        np.testing.assert_array_equal(p_a, p_b)
    assert l0 == l3

    # depth 0 writes step i at push i; depth 3 writes step i-3 at push i
    assert all(push == step for push, step, _ in w0)
    assert all(push == step + 3 for push, step, _ in w3 if push is not None)
    # exactly K steps were still in flight at the end (drained)
    assert sum(1 for push, _, _ in w3 if push is None) == 3


# ------------------------------------------------------------------ rollback
@pytest.mark.chaos
def test_rollback_quarantines_every_inflight_idx_set():
    """Satellite regression: the quarantine write must cover EVERY in-flight
    step's idx — the tripped entry's AND all entries still in the ring —
    exercised through the utils/faults.py nan_loss poison point with the
    SHARED RingCommitter protocol the three train loops use."""
    memory = PrioritizedReplay(
        512, (44, 44), history=2, n_step=3, gamma=0.9, lanes=4,
        priority_exponent=1.0, seed=0,
    )
    rng = np.random.default_rng(0)
    for t in range(40):
        memory.append_batch(
            rng.integers(0, 255, (4, 44, 44), dtype=np.uint8),
            rng.integers(0, A, 4),
            np.ones(4, np.float32),
            np.zeros(4, bool),
        )
    learn = jax.jit(build_learn_step(CFG, A))
    state = init_train_state(CFG, A, jax.random.PRNGKey(0))
    cfg = CFG.replace(max_nan_strikes=3, guard_snapshot_interval=1,
                      stall_timeout_s=0.0)
    sup = TrainSupervisor(cfg, injector=faults.FaultInjector("nan_loss@3"))
    ring = WritebackRing(2)
    key = jax.random.PRNGKey(5)

    sup.snapshot_if_due(0, lambda: (jax.tree.map(np.asarray, state),
                                    np.asarray(key)))
    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch

    quarantine_writes = []  # every (idx, zeros) write the committer issues
    real_update = memory.update_priorities

    def recording_update(idx, td_abs):
        if np.all(np.asarray(td_abs) == 0):
            quarantine_writes.append(np.asarray(idx))
        real_update(idx, td_abs)

    restored = {}

    def load_snapshot(s, k):
        restored["state"], restored["key"] = s, k

    committer = RingCommitter(ring, recording_update, sup, load_snapshot)

    pushed_idx = {}
    tripped_at = None
    for i in range(1, 8):
        sample = memory.sample(16, 0.6)
        batch = sup.poison_maybe(to_device_batch(sample))
        key, k = jax.random.split(key)
        state, info = learn(state, batch, k)
        pushed_idx[i] = sample.idx
        if not committer.commit(ring.push(i, sample.idx, info)):
            tripped_at = i
            break

    assert tripped_at is not None, "poisoned step never tripped the guard"
    # the poison fired at step 3; with depth 2 it retires at push 5, when
    # steps 4 and 5 are in flight -> ALL THREE idx sets quarantined
    assert tripped_at == 5
    assert len(quarantine_writes) == 3
    for step_no, written in zip((3, 4, 5), quarantine_writes):
        np.testing.assert_array_equal(written, pushed_idx[step_no])
    eps_floor = memory.eps ** 1.0  # omega = 1 -> (0 + eps)^1
    for step_no in (3, 4, 5):
        np.testing.assert_allclose(
            memory.tree.get(np.asarray(pushed_idx[step_no])), eps_floor,
            rtol=1e-6, err_msg=f"step {step_no} idx not quarantined",
        )
    assert sup.rollbacks == 1
    assert "state" in restored  # rolled back to the last-good snapshot
    assert len(ring) == 0  # ring flushed


# ----------------------------------------------------------- prefetch gauges
def test_prefetcher_exports_queue_gauges():
    import time

    reg = MetricRegistry()
    calls = {"n": 0}

    def slow_sample():
        calls["n"] += 1
        time.sleep(0.02)
        return calls["n"]

    pf = BatchPrefetcher(slow_sample, depth=2, device_put=False, registry=reg)
    try:
        got = [pf.get(timeout=5) for _ in range(4)]
        assert got == [1, 2, 3, 4]
        # consumer outran the 20ms sampler at least once -> starvation signal
        assert reg.counter("prefetch_empty_wait_total", "prefetch").get() >= 1
        snap = reg.histogram("prefetch_empty_wait_s", "prefetch").snapshot()
        assert snap["count"] >= 1
        # queue depth gauge is live (0..2)
        assert 0 <= reg.gauge("prefetch_queue_depth", "prefetch").get() <= 2
    finally:
        pf.close()

