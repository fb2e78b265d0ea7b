"""Device-resident sample frontier (replay/frontier.py; ISSUE 6).

Seeded equivalence + fencing suite:

1. distribution — device-frontier draws match the host ``ShardedReplay``
   sample distribution (both chi-squared against the EXACT proportional
   probabilities over priority bins);
2. IS weights — the device kernel's fp32 weights agree with the host
   ``(N P(i))^-beta / max`` formula computed in f64;
3. write-back parity — after K-lagged retirements interleaved with appends,
   ``reconcile()`` leaves the host sum-trees equal to a twin replay that
   took the same updates through the host path;
4. drop -> readmit — epoch fencing of the mirror: a dead shard's slice is
   zeroed (draws exclude it, lagged write-backs cannot resurrect it) and
   readmission refreshes it from the host tree;
5. the apex loop runs tier-1 under ``forbid_host_sync()`` with
   ``device_sampling=on`` — zero per-step host sampling syncs — and host
   ``sample()`` itself is a member of the forbidden set;
6. ``device_sampling=off`` and ``sample_ahead_depth=0`` both reproduce the
   host-path trajectory bitwise (the PR-5 behaviour).
"""

import json
import os

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
from rainbow_iqn_apex_tpu.replay.frontier import DeviceSampleFrontier
from rainbow_iqn_apex_tpu.utils import hostsync

FRAME = (12, 12)


def _filled_memory(shards=2, cap=512, lanes=4, seed=0, ticks=None):
    m = ShardedReplay.build(
        shards, cap, lanes, frame_shape=FRAME, history=2, n_step=3,
        gamma=0.9, seed=seed,
    )
    rng = np.random.default_rng(seed + 1)
    for _ in range(ticks if ticks is not None else cap // lanes):
        m.append_batch(
            rng.integers(0, 255, (lanes, *FRAME), dtype=np.uint8),
            rng.integers(0, 4, lanes),
            rng.normal(size=lanes).astype(np.float32),
            rng.random(lanes) < 0.02,
            priorities=rng.random(lanes) + 0.05,
        )
    return m


def _exact_probs(m: ShardedReplay) -> np.ndarray:
    leaves = np.concatenate([
        s.tree.tree[s.tree.span:s.tree.span + s.capacity] for s in m.shards
    ])
    return leaves / leaves.sum()


def _chi_square(counts: np.ndarray, expected: np.ndarray) -> float:
    keep = expected > 0
    return float(
        ((counts[keep] - expected[keep]) ** 2 / expected[keep]).sum()
    )


# ------------------------------------------------------------- distribution
def test_draw_matches_host_sample_distribution_chi_square():
    """Both samplers drawn many times land within the chi-square acceptance
    band of the EXACT proportional distribution, binned so every bin has a
    healthy expected count.  (Stratified draws have lower variance than iid
    multinomial, so the 99.9% critical value is a generous band.)"""
    m = _filled_memory()
    f = DeviceSampleFrontier.from_sharded(m, seed=7)
    p = _exact_probs(m)
    n_slots = p.size
    bins = 32
    bin_of = (np.arange(n_slots) * bins) // n_slots
    draws = 20_000
    B = 50

    dev_counts = np.zeros(bins)
    for _ in range(draws // (B * f.draw_block)):
        blk = f.draw(B, 0.5, len(m))
        idx = np.asarray(blk.idx).ravel()
        np.add.at(dev_counts, bin_of[idx], 1)
    n_dev = int(dev_counts.sum())

    host_counts = np.zeros(bins)
    for _ in range(draws // B):
        s = m.sample(B, 0.5)
        np.add.at(host_counts, bin_of[s.idx], 1)
    n_host = int(host_counts.sum())

    exp_bins = np.zeros(bins)
    np.add.at(exp_bins, bin_of, p)
    crit = 61.1  # chi2 df=31, alpha=0.001
    chi_dev = _chi_square(dev_counts, exp_bins * n_dev)
    chi_host = _chi_square(host_counts, exp_bins * n_host)
    assert chi_dev < crit, f"device draw chi2 {chi_dev:.1f} >= {crit}"
    assert chi_host < crit, f"host draw chi2 {chi_host:.1f} >= {crit}"


def test_is_weights_match_host_formula_fp32():
    m = _filled_memory()
    f = DeviceSampleFrontier.from_sharded(m, seed=3)
    beta = 0.6
    blk = f.draw(64, beta, len(m))
    idx = np.asarray(blk.idx)
    w_dev = np.asarray(blk.weight)
    leaves = np.concatenate([
        s.tree.tree[s.tree.span:s.tree.span + s.capacity] for s in m.shards
    ])  # f64 host truth
    total = leaves.sum()
    for g in range(blk.groups):
        prob = np.maximum(leaves[idx[g]] / total, 1e-12)
        w_ref = (len(m) * prob) ** (-beta)
        w_ref = w_ref / w_ref.max()
        np.testing.assert_allclose(
            w_dev[g], w_ref.astype(np.float32), rtol=2e-4, atol=1e-6,
            err_msg=f"group {g} IS weights diverge from host formula",
        )


# ------------------------------------------------------- write-back parity
def test_writeback_parity_after_lagged_retirements():
    """K=2 lagged retirements through the mirror + interleaved appends, then
    reconcile(): the host trees must equal a twin replay that took the SAME
    appends and priority updates through the host path (fp32 tolerance —
    the mirror is f32, the host tree f64)."""
    mem_dev = _filled_memory(seed=11, ticks=96)
    mem_host = _filled_memory(seed=11, ticks=96)
    f = DeviceSampleFrontier.from_sharded(mem_dev, seed=5)
    rng = np.random.default_rng(2)
    lag_queue = []
    K = 2
    n_slots = len(mem_dev.shards) * mem_dev.shard_capacity

    def eligible_idx():
        leaves = np.concatenate([
            s.tree.tree[s.tree.span:s.tree.span + s.capacity]
            for s in mem_host.shards
        ])
        pool = np.flatnonzero(leaves > 0)
        return rng.choice(pool, size=min(16, pool.size), replace=False)

    def tick(mem):
        r = np.random.default_rng(1000)  # same stream for both twins
        frames = r.integers(0, 255, (4, *FRAME), dtype=np.uint8)
        mem.append_batch(
            frames, r.integers(0, 4, 4), np.ones(4, np.float32),
            np.zeros(4, bool), priorities=np.full(4, 0.3),
        )

    for step in range(12):
        idx = eligible_idx()
        td = rng.random(idx.size).astype(np.float32) + 0.01
        lag_queue.append((idx, td))
        if len(lag_queue) > K:  # retire the oldest, K steps late
            r_idx, r_td = lag_queue.pop(0)
            f.update(r_idx, r_td)
            mem_host.update_priorities(r_idx, r_td.astype(np.float64))
        if step % 3 == 0:  # appends interleave with lagged retirements
            tick(mem_dev)
            tick(mem_host)
    for r_idx, r_td in lag_queue:  # drain the tail
        f.update(r_idx, r_td)
        mem_host.update_priorities(r_idx, r_td.astype(np.float64))

    f.reconcile()
    for k, (sd, sh) in enumerate(zip(mem_dev.shards, mem_host.shards)):
        np.testing.assert_allclose(
            sd.tree.tree[sd.tree.span:sd.tree.span + sd.capacity],
            sh.tree.tree[sh.tree.span:sh.tree.span + sh.capacity],
            rtol=1e-5, atol=1e-7,
            err_msg=f"shard {k} leaves diverged after reconcile",
        )
        # reconcile re-seeds the fresh-item default from WRITTEN leaves
        assert sd.max_priority >= sd.tree.max_leaf(sd.filled, sd.lanes) - 1e-6
    assert f.reconciles == 1
    assert mem_dev.shard_capacity * len(mem_dev.shards) == n_slots


# ------------------------------------------------------------ epoch fencing
def test_drop_readmit_epoch_fences_mirror():
    m = _filled_memory(shards=2)
    f = DeviceSampleFrontier.from_sharded(m, seed=9)
    cap = m.shard_capacity
    stamp_before = f.stamp
    shard1 = np.arange(cap, 2 * cap)

    m.drop_shard(1)
    mirror = f.mirror_np()
    assert (mirror[cap:] == 0).all(), "dead shard slice not zeroed"
    assert (mirror[:cap] > 0).any()
    # draws renormalise over the survivor
    blk = f.draw(64, 0.5, len(m))
    assert (np.asarray(blk.idx) < cap).all(), "draw returned dead-shard slots"
    # a lagged write-back to the dead shard must NOT resurrect it
    f.update(shard1[:8], np.full(8, 5.0, np.float32))
    assert (f.mirror_np()[cap:] == 0).all(), "write-back resurrected dead shard"
    # in-flight batches drawn before the drop read as stale
    assert f.stale_rows(shard1[:8], stamp_before) == 8
    assert f.stale_rows(np.arange(8), stamp_before) == 0

    m.readmit_shard(1)
    mirror = f.mirror_np()
    s1 = m.shards[1]
    np.testing.assert_allclose(
        mirror[cap:], s1.tree.tree[s1.tree.span:s1.tree.span + cap],
        rtol=1e-6,
        err_msg="readmitted slice not refreshed from the host tree",
    )


def test_restore_refreshes_mirror(tmp_path):
    m = _filled_memory()
    f = DeviceSampleFrontier.from_sharded(m, seed=1)
    f.update(np.arange(32), np.full(32, 3.0, np.float32))  # mirror diverges
    m.snapshot(str(tmp_path / "snap"))
    m.restore(str(tmp_path / "snap"))
    np.testing.assert_allclose(
        f.mirror_np(), np.concatenate([
            s.tree.tree[s.tree.span:s.tree.span + s.capacity]
            for s in m.shards
        ]).astype(np.float32), rtol=1e-6,
        err_msg="restore did not refresh the mirror from the host trees",
    )


# ------------------------------------------------------- sample-ahead push
def test_sample_ahead_pusher_serves_assembled_batches():
    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.replay.frontier import make_batch_assembler
    from rainbow_iqn_apex_tpu.utils.prefetch import SampleAheadPusher

    m = _filled_memory()
    reg = MetricRegistry()
    f = DeviceSampleFrontier.from_sharded(m, registry=reg, seed=4)
    pusher = SampleAheadPusher(
        f, make_batch_assembler(m, to_device_batch), 16,
        lambda: 0.5, lambda: len(m), depth=2, registry=reg,
    )
    try:
        for _ in range(3):
            idx, batch = pusher.get(timeout=30)
            assert idx.shape == (16,) and idx.dtype == np.int64
            assert batch.obs.shape == (16, *FRAME, 2)
            assert batch.weight.shape == (16,)
            assert float(np.asarray(batch.weight).max()) == pytest.approx(1.0)
        assert reg.gauge("sample_ahead_queue_depth", "prefetch").get() >= 0
    finally:
        pusher.close()


def test_gather_time_cursor_fence_zeroes_invalidated_rows():
    """Lap-straddle regression: a drawn index whose slot the ring cursor
    invalidated between DRAW and GATHER (host-tree leaf now 0: history or
    n-step window crosses the cursor) must be served with IS weight 0 —
    never trained on as a frame-mixed transition — and counted as stale."""
    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.replay.frontier import make_batch_assembler

    m = _filled_memory(shards=1, cap=256, lanes=4)
    reg = MetricRegistry()
    assemble = make_batch_assembler(m, to_device_batch, registry=reg)
    s0 = m.shards[0]
    leaves = s0.tree.tree[s0.tree.span:s0.tree.span + s0.capacity]
    bad = np.flatnonzero(leaves == 0)[:4]   # cursor-invalidated slots
    good = np.flatnonzero(leaves > 0)[:4]   # still-eligible slots
    assert bad.size == 4 and good.size == 4
    idx = np.sort(np.concatenate([bad, good]))
    weight = np.ones(8, np.float32)

    out_idx, batch = assemble(idx, weight)
    w = np.asarray(batch.weight)
    bad_rows = np.isin(out_idx, bad)
    assert (w[bad_rows] == 0.0).all(), "invalidated rows kept nonzero weight"
    assert (w[~bad_rows] == 1.0).all()
    assert reg.counter(
        "sample_ahead_stale_indices_total", "prefetch"
    ).get() == 4
    with pytest.raises(IndexError):  # loud, not garbage, on bad global ids
        m.assemble_global(np.asarray([10**9]), np.ones(1, np.float32))


# ------------------------------------------------ forbidden-sync membership
def test_host_sampling_joined_the_forbidden_set():
    m = _filled_memory()
    with hostsync.forbid_host_sync():
        with pytest.raises(hostsync.HostSyncError):
            m.sample(8, 0.5)
        with pytest.raises(hostsync.HostSyncError):
            m.shards[0].sample(8, 0.5)
        with hostsync.sanctioned():  # cold paths may still sample
            assert m.sample(8, 0.5).obs.shape == (8, *FRAME, 2)
    assert m.sample(8, 0.5).obs.shape == (8, *FRAME, 2)


def _apex_cfg(tmp_path, run_id, **kw):
    return Config(
        env_id="toy:catch", compute_dtype="float32", frame_height=44,
        frame_width=44, history_length=2, hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, learning_rate=1e-3, multi_step=3, gamma=0.9,
        memory_capacity=2048, learn_start=256, frames_per_learn=2,
        target_update_period=100, num_envs_per_actor=8, metrics_interval=50,
        eval_interval=0, checkpoint_interval=0, eval_episodes=2,
        stall_timeout_s=0.0, writeback_depth=2, replay_shards=2,
        weight_publish_interval=100, seed=3, run_id=run_id,
        results_dir=str(tmp_path / run_id / "results"),
        checkpoint_dir=str(tmp_path / run_id / "ckpt"),
        **kw,
    )


def test_apex_loop_zero_host_sampling_syncs(tmp_path):
    """ACCEPTANCE: the full apex loop — frontier draws, sample-ahead pusher,
    mirror write-back, reconcile at drains — runs end to end inside
    ``forbid_host_sync()`` with device sampling on.  Host ``sample()`` is
    itself forbidden in that region, so the pass proves the learner thread
    issued ZERO per-step host sampling syncs."""
    from rainbow_iqn_apex_tpu.parallel.apex import train_apex

    cfg = _apex_cfg(tmp_path, "dev_on", device_sampling=True,
                    sample_ahead_depth=2)
    with hostsync.forbid_host_sync():
        summary = train_apex(cfg, max_frames=700)
    assert summary["learn_steps"] > 0
    assert summary["rollbacks"] == 0


def _learn_rows(cfg):
    path = os.path.join(cfg.results_dir, cfg.run_id, "metrics.jsonl")
    rows = [json.loads(line) for line in open(path) if line.strip()]
    return [
        (r["step"], r["loss"], r["q_mean"])
        for r in rows if r.get("kind") == "learn"
    ]


def test_device_sampling_off_and_depth0_reproduce_host_path(tmp_path):
    """ACCEPTANCE: ``device_sampling=off`` and ``sample_ahead_depth=0`` both
    take the PR-5 host sampling path — identical learn-row trajectories
    (loss/q_mean bitwise equal at fixed seeds)."""
    from rainbow_iqn_apex_tpu.parallel.apex import train_apex

    # prefetch_depth=0 on both sides, for tests/test_league.py's reason: the
    # host path's prefetch thread (utils/prefetch.py) samples ahead of the
    # main thread's appends and priority write-backs, so what a batch sees
    # goes by thread timing (this case failed in most runs on a loaded box:
    # a learn row that differed at step 200 and agreed before).  Staleness by
    # the pipeline's depth is the documented semantics; WHICH depth a batch
    # saw is not reproducible with the thread.  writeback_depth=2 stays: that
    # ring retires on the main thread at a fixed depth.
    s_off = train_apex(
        _apex_cfg(tmp_path, "off", device_sampling=False, prefetch_depth=0),
        max_frames=600)
    s_d0 = train_apex(
        _apex_cfg(tmp_path, "d0", device_sampling=True, sample_ahead_depth=0,
                  prefetch_depth=0),
        max_frames=600)
    assert s_off["learn_steps"] == s_d0["learn_steps"] > 0
    rows_off = _learn_rows(_apex_cfg(tmp_path, "off"))
    rows_d0 = _learn_rows(_apex_cfg(tmp_path, "d0"))
    assert rows_off and rows_off == rows_d0


def test_apex_r2d2_device_sampling_smoke(tmp_path):
    """The sequence-replay flavour of the frontier drives the R2D2 apex
    loop end to end (single mirrored tree, emitted-sequence staging)."""
    from rainbow_iqn_apex_tpu.parallel.apex_r2d2 import train_apex_r2d2

    cfg = Config(
        architecture="r2d2", env_id="toy:catch", compute_dtype="float32",
        frame_height=24, frame_width=24, history_length=1, hidden_size=32,
        lstm_size=32, r2d2_burn_in=4, r2d2_seq_len=8, r2d2_overlap=4,
        batch_size=8, learning_rate=1e-3, multi_step=1, gamma=0.9,
        memory_capacity=4096, learn_start=64, frames_per_learn=4,
        target_update_period=100, num_envs_per_actor=8, metrics_interval=20,
        eval_interval=0, checkpoint_interval=0, eval_episodes=1,
        stall_timeout_s=0.0, device_sampling=True, sample_ahead_depth=2,
        writeback_depth=2, num_tau_samples=4, num_tau_prime_samples=4,
        num_quantile_samples=4, num_cosines=8, seed=5,
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    summary = train_apex_r2d2(cfg, max_frames=600)
    assert summary["learn_steps"] > 0
    assert summary["sequences"] > 0
