"""Replay-ratio > 1 (ISSUE 12): the fused K-pass clipped-reuse learn step.

Coverage map (the ISSUE's test satellite):
1. `replay_ratio=1` (default) is the UNWRAPPED single-pass step — bitwise
   identical trajectory vs an independently hand-rolled PR-11 reference.
2. Clip math hand-computed on a 2-row batch: the fused K=2 executable
   matches a manual pass-1 -> ratio -> clip -> scaled-pass-2 composition,
   including the clip fraction, with the clip demonstrably ENGAGED.
3. K>1 priorities lag exactly one SAMPLE (not one pass): one ring entry
   per fused dispatch, final-pass |TD|, one write-back per sample.
4. Composition: multitask (task-conditioned learner) and device_sampling
   (frontier + sample-ahead pusher) both run end to end at K=2.
5. Ring-drain at publish boundaries mid-reuse: cadences NOT divisible by K
   still fire exactly once per crossing (cadence_hit), publishes/evals/
   checkpoints drain cleanly between fused dispatches.
6. The loops that do not implement reuse reject K > 1 with a reasoned
   error instead of silently training at the wrong rate.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.ops.learn import (
    Batch,
    TrainState,
    build_learn_step,
    init_train_state,
    loss_and_priorities,
    make_network,
    make_optimizer,
    make_policy_logp,
    make_reuse_learn_step,
)
from rainbow_iqn_apex_tpu.utils.writeback import cadence_hit

A = 4
CFG = Config(
    compute_dtype="float32", frame_height=44, frame_width=44,
    history_length=2, hidden_size=32, num_cosines=8, num_tau_samples=4,
    num_tau_prime_samples=4, num_quantile_samples=4, batch_size=16,
    multi_step=3, gamma=0.9, target_update_period=3,
)


def _batch(n_rows=16, seed=3):
    rng = np.random.default_rng(seed)
    return Batch(
        obs=jnp.asarray(rng.integers(0, 255, (n_rows, 44, 44, 2), dtype=np.uint8)),
        action=jnp.asarray(rng.integers(0, A, n_rows).astype(np.int32)),
        reward=jnp.asarray(rng.normal(size=n_rows).astype(np.float32)),
        next_obs=jnp.asarray(
            rng.integers(0, 255, (n_rows, 44, 44, 2), dtype=np.uint8)),
        discount=jnp.asarray(np.full(n_rows, 0.9, np.float32)),
        weight=jnp.asarray(
            rng.uniform(0.5, 1.0, n_rows).astype(np.float32)),
    )


def _tree_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ----------------------------------------------------------- cadence_hit
def test_cadence_hit_k1_is_exact_modulo():
    for step in range(1, 50):
        for interval in (0, 1, 5, 20):
            assert cadence_hit(step, interval, 1) == (
                bool(interval) and step % interval == 0)


def test_cadence_hit_fires_once_per_crossing_at_k():
    # K=4 steps land on 4, 8, 12, ...; interval 6 is NOT divisible by K —
    # every multiple of 6 must still be crossed exactly once
    k, interval = 4, 6
    hits = [s for s in range(k, 100, k) if cadence_hit(s, interval, k)]
    crossings = [s for s in range(k, 100, k)
                 if s // interval > (s - k) // interval]
    assert hits == crossings and len(hits) > 0


# ------------------------------------------------- K=1 bitwise reference
def test_k1_default_is_unwrapped_and_bitwise_vs_reference():
    """cfg.replay_ratio=1 (default) must run the PR-11 single-pass math
    exactly: compare 4 steps against an independently composed reference
    (loss_and_priorities + optax + the scheduled target copy, re-rolled
    here) — params, opt_state, priorities all bitwise equal, and the info
    dict carries NO reuse keys."""
    cfg = CFG  # default replay_ratio=1
    net, tx = make_network(cfg, A), make_optimizer(cfg)

    def reference(state, batch, key):
        def loss_fn(params):
            return loss_and_priorities(
                net, cfg, params, state.target_params, batch, key)

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        step = state.step + 1
        do_copy = (step % cfg.target_update_period == 0).astype(jnp.float32)
        target = jax.tree.map(
            lambda t, o: do_copy * o + (1.0 - do_copy) * t,
            state.target_params, params)
        return TrainState(params=params, target_params=target,
                          opt_state=opt_state, step=step), aux["td_abs"]

    learn = jax.jit(build_learn_step(cfg, A))
    ref = jax.jit(reference)
    s_got = init_train_state(cfg, A, jax.random.PRNGKey(0))
    s_ref = init_train_state(cfg, A, jax.random.PRNGKey(0))
    base = jax.random.PRNGKey(7)
    for i in range(4):
        b = _batch(seed=i)
        k = jax.random.fold_in(base, i)
        s_got, info = learn(s_got, b, k)
        s_ref, pri_ref = ref(s_ref, b, k)
        assert "clip_frac" not in info and "replay_ratio" not in info
        assert np.array_equal(np.asarray(info["priorities"]),
                              np.asarray(pri_ref))
    assert int(s_got.step) == 4
    assert _tree_equal(s_got.params, s_ref.params)
    assert _tree_equal(s_got.opt_state, s_ref.opt_state)
    assert _tree_equal(s_got.target_params, s_ref.target_params)


# ------------------------------------------------- hand-computed clip math
def test_fused_k2_matches_hand_composed_clipped_passes():
    """The fused K=2 executable == pass-1 (plain), then ratio/clip/pass-2
    composed BY HAND on a 2-row batch: behavior log-probs from the shared
    ratio key, ratio = exp(logp_now - logp_behavior), clipped to
    [1/c, c], pass-2 IS weights scaled by the clipped ratio.  A huge
    learning rate + a tight clip force real drift, so the clip ENGAGES
    (clip_frac > 0) and the hand numbers are non-trivial."""
    cfg = CFG.replace(replay_ratio=2, reuse_clip=1.01, learning_rate=0.5)
    net = make_network(cfg, A)
    single = build_learn_step(cfg.replace(replay_ratio=1), A)
    logp_fn = make_policy_logp(net, cfg)
    fused = jax.jit(make_reuse_learn_step(cfg, single, logp_fn))
    pass_jit = jax.jit(single)

    state0 = init_train_state(cfg, A, jax.random.PRNGKey(0))
    batch = _batch(n_rows=2, seed=5)
    key = jax.random.PRNGKey(9)

    s_fused, info = fused(
        init_train_state(cfg, A, jax.random.PRNGKey(0)), batch, key)

    # hand composition — the exact recipe make_reuse_learn_step documents
    k_ratio, k_loop = jax.random.split(key)
    behav = logp_fn(state0.params, batch, k_ratio)
    s1, _i1 = pass_jit(state0, batch, jax.random.fold_in(k_loop, 0))
    logp2 = logp_fn(s1.params, batch, k_ratio)
    ratio = np.exp(np.asarray(logp2, np.float64)
                   - np.asarray(behav, np.float64))
    clipped = np.clip(ratio, 1.0 / cfg.reuse_clip, cfg.reuse_clip)
    clip_frac_hand = float(np.mean(ratio != clipped))
    s2, i2 = pass_jit(
        s1, batch, jax.random.fold_in(k_loop, 1),
        jnp.asarray(clipped.astype(np.float32)),
    )

    assert clip_frac_hand > 0.0  # the clip actually engaged
    assert float(info["clip_frac"]) == pytest.approx(clip_frac_hand,
                                                     abs=1e-6)
    assert int(s_fused.step) == 2
    assert int(info["replay_ratio"]) == 2 and int(info["reuse_index"]) == 1
    for got, want in zip(jax.tree.leaves(s_fused.params),
                         jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(info["priorities"]), np.asarray(i2["priorities"]),
        rtol=2e-5, atol=2e-6)


def test_zero_drift_means_ratio_one_and_zero_clip_frac():
    """lr=0: params never move, so every reuse pass's ratio is EXACTLY 1
    (shared ratio key — no tau/noise resampling noise) and nothing clips;
    K passes at lr=0 leave params bitwise unchanged while step advances
    K."""
    cfg = CFG.replace(replay_ratio=3, reuse_clip=1.0000001,
                      learning_rate=0.0, max_grad_norm=0.0)
    learn = jax.jit(build_learn_step(cfg, A))
    s0 = init_train_state(cfg, A, jax.random.PRNGKey(0))
    s1, info = learn(s0, _batch(), jax.random.PRNGKey(1))
    assert float(info["clip_frac"]) == 0.0
    assert int(s1.step) == 3
    assert _tree_equal(s0.params, s1.params)


# ------------------------------------- priorities lag samples, not passes
def test_priorities_written_once_per_sample_final_pass(tmp_path,
                                                       monkeypatch):
    """K=2 over the real train() loop: every fused dispatch pushes ONE ring
    entry, so the priority write-back stream has exactly learn_steps / K
    entries (one per SAMPLE, batch-sized each) — priorities lag by the
    ring depth in samples, never per-pass."""
    from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay
    from rainbow_iqn_apex_tpu.train import train

    writes = []
    orig = PrioritizedReplay.update_priorities

    def spy(self, idx, priorities):
        writes.append(np.asarray(priorities).shape)
        return orig(self, idx, priorities)

    monkeypatch.setattr(PrioritizedReplay, "update_priorities", spy)
    cfg = Config(
        env_id="toy:chain", compute_dtype="float32", history_length=2,
        hidden_size=32, num_cosines=8, num_tau_samples=4,
        num_tau_prime_samples=4, num_quantile_samples=4, batch_size=16,
        learning_rate=1e-3, multi_step=3, gamma=0.9, memory_capacity=2048,
        learn_start=64, frames_per_learn=4, replay_ratio=2,
        target_update_period=64, num_envs_per_actor=4, metrics_interval=20,
        eval_interval=0, checkpoint_interval=0, eval_episodes=2,
        stall_timeout_s=0.0, writeback_depth=1, seed=11,
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    summary = train(cfg, max_frames=256)
    assert summary["rollbacks"] == 0
    samples = 256 // cfg.frames_per_learn
    assert summary["learn_steps"] == cfg.replay_ratio * samples
    assert len(writes) == samples  # once per SAMPLE, not per pass
    assert all(shape == (cfg.batch_size,) for shape in writes)


# -------------------------------------------------------- loop composition
def _apex_cfg(tmp_path, run_id, **kw):
    base = dict(
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
    )
    base.update(kw)
    return Config(**base)


def _rows(cfg):
    path = os.path.join(cfg.results_dir, cfg.run_id, "metrics.jsonl")
    return [json.loads(line) for line in open(path) if line.strip()]


def test_reuse_composes_with_device_sampling(tmp_path):
    """device_sampling + replay_ratio=2: the frontier draw / sample-ahead
    push / mirror write-back pipeline feeds fused K-pass dispatches — one
    popped batch per K learn steps — with zero forbidden host syncs."""
    from rainbow_iqn_apex_tpu.parallel.apex import train_apex
    from rainbow_iqn_apex_tpu.utils import hostsync

    cfg = _apex_cfg(tmp_path, "reuse_dev", device_sampling=True,
                    sample_ahead_depth=2, replay_ratio=2)
    with hostsync.forbid_host_sync():
        summary = train_apex(cfg, max_frames=448)
    assert summary["rollbacks"] == 0
    assert summary["learn_steps"] == 2 * (
        summary["frames"] // cfg.frames_per_learn)
    learn_rows = [r for r in _rows(cfg) if r["kind"] == "learn"]
    assert learn_rows and all(
        r["replay_ratio"] == 2 for r in learn_rows)


@pytest.mark.multitask
def test_reuse_composes_with_multitask(tmp_path):
    """2-game task-conditioned apex at replay_ratio=2: the masked-logp
    reuse wrapper (multitask/ops.py) drives the whole suite through one
    fused executable; learn rows carry the reuse fields, games rows keep
    their per-game story."""
    from rainbow_iqn_apex_tpu.parallel.apex import train_apex

    cfg = _apex_cfg(
        tmp_path, "reuse_mt", games="toy:catch,toy:chain",
        frames_per_learn=4, replay_ratio=2, replay_shards=1,
        memory_capacity=4096,
    )
    summary = train_apex(cfg, max_frames=768)
    assert summary["rollbacks"] == 0
    assert summary["learn_steps"] == 2 * (768 // cfg.frames_per_learn)
    rows = _rows(cfg)
    learn_rows = [r for r in rows if r["kind"] == "learn"]
    assert learn_rows and all(r["replay_ratio"] == 2 for r in learn_rows)
    assert any(r["kind"] == "games" for r in rows)


def test_publish_boundaries_mid_reuse_drain_cleanly(tmp_path):
    """K=4 with publish/eval/checkpoint cadences NOT divisible by K: every
    crossing still fires once (cadence_hit), each boundary drains the ring
    between fused dispatches, and the run completes with versions
    advancing.  The learn rows' reuse fields fold into health rows +
    obs_report's pipeline line + health_attribution's tally."""
    from rainbow_iqn_apex_tpu.obs.attribution import health_attribution
    from rainbow_iqn_apex_tpu.parallel.apex import train_apex
    from scripts.lint_jsonl import lint_line
    from scripts.obs_report import aggregate

    cfg = _apex_cfg(
        tmp_path, "reuse_pub", replay_ratio=4, reuse_clip=1.5,
        weight_publish_interval=6, eval_interval=150,
        checkpoint_interval=202, guard_snapshot_interval=10,
        metrics_interval=10, eval_episodes=1,
    )
    summary = train_apex(cfg, max_frames=288)
    assert summary["rollbacks"] == 0
    assert summary["learn_steps"] == 4 * (288 // cfg.frames_per_learn)

    path = os.path.join(cfg.results_dir, cfg.run_id, "metrics.jsonl")
    rows = []
    for line in open(path):
        assert lint_line(line) is None, line
        rows.append(json.loads(line))
    learn_rows = [r for r in rows if r["kind"] == "learn"]
    assert learn_rows
    for r in learn_rows:
        assert r["replay_ratio"] == 4 and r["reuse_index"] in (None, 3)
    # publishes happened repeatedly despite 6 % 4 != 0
    health = [r for r in rows if r["kind"] == "health"
              and r.get("weights_version") is not None]
    assert health and health[-1]["weights_version"] >= 3
    assert health[-1].get("replay_ratio") == 4
    # eval crossings at interval 10 with step jumps of 4
    assert sum(1 for r in rows if r["kind"] == "eval") >= 2

    report = aggregate(rows)
    assert report["pipeline"]["replay_ratio"] == 4
    assert report["pipeline"]["reuse_clip_frac"] is not None
    tally = health_attribution(path)
    assert tally["reuse"]["rows"] == len(learn_rows)
    assert tally["reuse"]["replay_ratio"] == 4


# --------------------------------------------------------------- guards
def test_non_reuse_loops_reject_k_gt_1(tmp_path):
    from rainbow_iqn_apex_tpu.parallel.apex_r2d2 import train_apex_r2d2
    from rainbow_iqn_apex_tpu.train_anakin import train_anakin
    from rainbow_iqn_apex_tpu.train_anakin_r2d2 import train_anakin_r2d2
    from rainbow_iqn_apex_tpu.train_r2d2 import train_r2d2

    cfg = Config(replay_ratio=2, results_dir=str(tmp_path / "r"),
                 checkpoint_dir=str(tmp_path / "c"))
    for entry in (train_r2d2, train_anakin, train_anakin_r2d2,
                  train_apex_r2d2):
        with pytest.raises(ValueError, match="replay_ratio"):
            entry(cfg, max_frames=64)


def test_sub_k_cadence_interval_is_rejected(tmp_path):
    """An interval below K would fire on EVERY fused dispatch (cadence_hit
    crossings) and serialize the loop — the reuse loops reject it at start
    instead of silently degrading (0 = off stays allowed)."""
    from rainbow_iqn_apex_tpu.train import train
    from rainbow_iqn_apex_tpu.utils.writeback import check_reuse_cadences

    cfg = Config(replay_ratio=4, metrics_interval=3)
    with pytest.raises(ValueError, match="metrics_interval"):
        check_reuse_cadences(cfg, "metrics_interval")
    check_reuse_cadences(cfg.replace(metrics_interval=0), "metrics_interval")
    check_reuse_cadences(cfg.replace(replay_ratio=1), "metrics_interval")
    cfg = Config(
        env_id="toy:chain", compute_dtype="float32", history_length=2,
        hidden_size=32, num_cosines=8, num_tau_samples=4,
        num_tau_prime_samples=4, num_quantile_samples=4, batch_size=16,
        replay_ratio=4, eval_interval=2, num_envs_per_actor=4,
        results_dir=str(tmp_path / "r"), checkpoint_dir=str(tmp_path / "c"))
    with pytest.raises(ValueError, match="eval_interval"):
        train(cfg, max_frames=64)


def test_step_timer_units_count_sgd_steps_not_dispatches(monkeypatch):
    """The timing row must report SGD steps/s, not dispatches/s: a K=4
    reuse run laps the StepTimer once per fused dispatch but each lap
    covers 4 steps — `steps`/`steps_per_sec` scale by K while the per-lap
    percentiles stay per-dispatch."""
    import rainbow_iqn_apex_tpu.utils.profiling as profiling

    clock = iter(float(t) for t in range(100))  # 1s per lap, exactly
    monkeypatch.setattr(profiling.time, "perf_counter", lambda: next(clock))
    t1, t4 = profiling.StepTimer(warmup=0), profiling.StepTimer(warmup=0)
    for _ in range(5):
        t1.lap()
    for _ in range(5):
        t4.lap(units=4)
    s1, s4 = t1.stats(), t4.stats()
    assert s1["steps"] == 4 and s1["steps_per_sec"] == pytest.approx(1.0)
    assert s4["steps"] == 16 and s4["steps_per_sec"] == pytest.approx(4.0)
    assert s4["p50_s"] == pytest.approx(1.0)  # percentiles per DISPATCH


def test_sample_ahead_pusher_shrinks_draw_ahead_by_reuse():
    """One staged batch feeds K learn passes, so the pusher shrinks BOTH
    its staged-queue depth and the device-side draw-ahead ceil-wise by K —
    in one place, from the reuse= parameter (docs/PERFORMANCE.md)."""
    from rainbow_iqn_apex_tpu.utils.prefetch import SampleAheadPusher

    class _Block:
        idx = np.zeros((1, 4), np.int64)
        weight = np.ones((1, 4), np.float32)
        stamp = 0
        groups = 1

    class _Frontier:
        def draw(self, b, beta, n):
            return _Block()

        def stale_rows(self, idx, stamp):
            return 0

    pushers = []
    try:
        for reuse, draw_ahead, want in ((1, 2, 2), (4, 2, 1), (2, 3, 2)):
            p = SampleAheadPusher(
                _Frontier(), lambda i, w: (i, w), 4, lambda: 0.5,
                lambda: 16, depth=2, draw_ahead=draw_ahead, reuse=reuse,
            )
            pushers.append(p)
            assert p._draw_ahead == want, (reuse, draw_ahead)
            assert p.depth == max(-(-2 // reuse), 1), reuse
    finally:
        for p in pushers:
            p.close()
