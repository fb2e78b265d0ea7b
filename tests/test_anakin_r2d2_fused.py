"""Fused R2D2 Anakin (train_anakin_r2d2): recurrent actor + env + HBM
sequence replay + sequence learner in one scanned XLA graph.  Lifecycle
contract mirrors tests/test_anakin_fused.py; the sequence-replay semantics
are pinned by tests/test_device_sequence.py.
"""

import json
import os

import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.train_anakin_r2d2 import (
    _learn_cadence,
    train_anakin_r2d2,
)


def _cfg(tmp_path, **kw):
    base = dict(
        env_id="jaxgame:catch",
        architecture="r2d2",
        role="anakin",
        compute_dtype="float32",
        history_length=2,
        hidden_size=64,
        lstm_size=32,
        r2d2_burn_in=2,
        r2d2_seq_len=8,
        r2d2_overlap=4,
        batch_size=16,
        learning_rate=1e-3,
        multi_step=2,
        gamma=0.9,
        memory_capacity=4_000,  # -> 400 sequences of 10
        learn_start=256,  # -> warm at 25 sequences
        frames_per_learn=2,  # fps=16 frames/step = 2 ticks of 8 lanes
        target_update_period=100,
        num_envs_per_actor=8,
        anakin_segment_ticks=16,
        learner_devices=1,
        metrics_interval=50,
        eval_interval=0,
        checkpoint_interval=0,
        eval_episodes=10,
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "ckpt"),
        seed=3,
    )
    base.update(kw)
    return Config(**base)


def test_cadence_static_mapping(tmp_path):
    # period ticks per learn step when frames/step >= lanes
    assert _learn_cadence(_cfg(tmp_path)) == (2, 1)
    # k learn steps per tick when lanes exceed the frame budget
    assert _learn_cadence(
        _cfg(tmp_path, num_envs_per_actor=32, frames_per_learn=2, r2d2_seq_len=8)
    ) == (1, 2)
    with pytest.raises(ValueError, match="divide one another"):
        _learn_cadence(
            _cfg(tmp_path, num_envs_per_actor=12, frames_per_learn=2,
                 r2d2_seq_len=8)
        )


@pytest.mark.slow
def test_fused_r2d2_smoke_end_to_end(tmp_path):
    cfg = _cfg(tmp_path, checkpoint_interval=50)
    summary = train_anakin_r2d2(cfg, max_frames=2_000)
    assert summary["frames"] >= 2_000
    # 250 ticks at period 2, minus the ~32-tick warmup
    assert summary["learn_steps"] > 80
    assert np.isfinite(summary["eval_score_mean"])
    rows = [json.loads(l) for l in open(
        os.path.join(cfg.results_dir, cfg.run_id, "metrics.jsonl"))]
    kinds = {r["kind"] for r in rows}
    assert "learn" in kinds and "eval" in kinds
    train_rows = [r for r in rows if r["kind"] == "learn"]
    assert all(np.isfinite(r["loss"]) for r in train_rows)


def test_learn_row_reports_append_emit_tick_share(tmp_path):
    """On freeway the lanes run in lockstep (no terminals, time limit far
    off): with 10-step windows every 6 ticks the append's conditional takes
    its emit branch on ticks 10, 16, 22, ... and skips the rest.  Each
    `learn` row reports the share of emitting ticks since the row before."""
    cfg = _cfg(tmp_path, env_id="jaxgame:freeway", metrics_interval=1,
               learn_start=80, hidden_size=32, lstm_size=16, batch_size=8)
    T, lanes = cfg.anakin_segment_ticks, cfg.num_envs_per_actor
    train_anakin_r2d2(cfg, max_frames=6 * T * lanes)
    rows = [json.loads(l) for l in open(
        os.path.join(cfg.results_dir, cfg.run_id, "metrics.jsonl"))]
    learn = [r for r in rows if r["kind"] == "learn"]
    assert len(learn) >= 3
    emitting = lambda t: t >= 10 and (t - 10) % 6 == 0  # noqa: E731
    before = 0
    for r in learn:
        now = r["frames"] // lanes
        want = sum(emitting(t) for t in range(before + 1, now + 1))
        assert r["append_emit_tick_share"] == pytest.approx(
            want / (now - before))
        assert 0.0 < r["append_emit_tick_share"] < 1.0
        before = now


def _spy_on_add_program(monkeypatch):
    """The programs a loop registers with its `TraceWindow`, armed or not."""
    from rainbow_iqn_apex_tpu.obs import TraceWindow

    registered = []
    monkeypatch.setattr(TraceWindow, "add_program",
                        lambda self, text: registered.append(text))
    return registered


def test_hostfed_anakin_r2d2_smoke(tmp_path, monkeypatch):
    """Non-jaxgame envs dispatch to the host-fed loop: env on host, sequence
    ring + LSTM + stack device-resident, lag-one appends."""
    cfg = _cfg(
        tmp_path,
        env_id="toy:catch",
        hidden_size=32,
        lstm_size=16,
        memory_capacity=2_000,
        learn_start=200,
        anakin_segment_ticks=8,
    )
    registered = _spy_on_add_program(monkeypatch)
    summary = train_anakin_r2d2(cfg, max_frames=1_200)
    assert summary["frames"] >= 1_200
    assert summary["learn_steps"] > 20
    assert np.isfinite(summary["eval_score_mean"])
    # both programs are registered for the 'device_time' row, and their
    # texts name the scopes of the work inside them
    from rainbow_iqn_apex_tpu.obs import device_scopes as ds

    act, learn = (text() for text in registered)
    assert ds.module_name(act) != ds.module_name(learn)
    assert any(ds.LSTM_SCAN in p for p in ds.instruction_scopes(act).values())
    assert any(ds.LEARN_STEP in p and ds.OPTIMIZER in p
               for p in ds.instruction_scopes(learn).values())


@pytest.mark.slow
def test_fused_r2d2_resume_continues_counters(tmp_path):
    cfg = _cfg(tmp_path, checkpoint_interval=25, snapshot_replay=True)
    first = train_anakin_r2d2(cfg, max_frames=1_200)
    cfg2 = cfg.replace(resume=True)
    second = train_anakin_r2d2(cfg2, max_frames=2_400)
    assert second["frames"] >= 2_400
    assert second["learn_steps"] > first["learn_steps"]


@pytest.mark.slow
def test_fused_r2d2_sharded_over_mesh(tmp_path):
    """learner_devices>1: env lanes, LSTM lanes, and per-shard sequence rings
    all dp-sharded in the one fused graph (virtual 8-device mesh)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg = _cfg(
        tmp_path,
        hidden_size=32,
        memory_capacity=2_560,  # -> 256 sequences, 64/shard
        learn_start=160,
        anakin_segment_ticks=8,
        learner_devices=4,
    )
    summary = train_anakin_r2d2(cfg, max_frames=1_600)
    assert summary["frames"] >= 1_600
    assert summary["learn_steps"] > 40
    assert np.isfinite(summary["eval_score_mean"])


def test_entry_point_dispatches_anakin_r2d2(tmp_path):
    import train_agent_apex

    rc = train_agent_apex.main([
        "--role", "anakin", "--architecture", "r2d2",
        "--env-id", "jaxgame:catch", "--compute-dtype", "float32",
        "--history-length", "2", "--hidden-size", "32", "--lstm-size", "16",
        "--r2d2-burn-in", "2", "--r2d2-seq-len", "8", "--r2d2-overlap", "4",
        "--batch-size", "8", "--multi-step", "2", "--memory-capacity", "2000",
        "--learn-start", "200", "--frames-per-learn", "2",
        "--num-envs-per-actor", "8", "--anakin-segment-ticks", "8",
        "--learner-devices", "1", "--eval-episodes", "4",
        "--eval-interval", "0", "--checkpoint-interval", "0",
        "--t-max", "640",
        "--results-dir", str(tmp_path / "results"),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert rc == 0


@pytest.mark.slow
def test_fused_r2d2_learns_catch(tmp_path):
    """Learning proof at the recipe the committed evidence run measured
    (results/r2d2_fused_learning/, scripts/run_r2d2_evidence.py, round 4):
    hidden 64 / lstm 64 / history 1 / seq 10 / batch 16, seed 7 — full
    curve eval -0.9 at 5k frames, 0.0 at 6.8k, 0.7 at 8.1k, 0.85 at 11.3k,
    **1.0 (40/40) from 12.6k through the 16k finish** — A/B parity with
    the host R2D2's perfect solve (test_r2d2.py: 1.0 at 20k frames).
    Config history: the round-3 cut (hidden 128 / lstm 64 / history 2) ran
    at 0.4 fps — unfinishable here — and a quarter-cost lstm-32 /
    history-2 variant stayed AT RANDOM through 4k frames; lstm 64 (the
    host-proven memory size) with history 1 is the working recipe — catch
    is positionally observable per frame, so the frame stack is the right
    cost to shed, not the LSTM.  10k frames at ~1.5 fps ≈ 1.9 h on this
    1-core sandbox: long but completable, and the measured curve puts the
    >0.3 bar well inside the 8.1k-frame measurement (0.7)."""
    cfg = _cfg(
        tmp_path,
        history_length=1,
        hidden_size=64,
        lstm_size=64,
        r2d2_seq_len=10,
        learning_rate=2e-3,
        memory_capacity=16_000,
        learn_start=512,
        frames_per_learn=1,  # 10 frames/step = 1 tick -> dense updates
        num_envs_per_actor=10,  # lanes must equal frames_per_learn * seq_len
        anakin_segment_ticks=32,
        target_update_period=100,
        eval_episodes=40,
        seed=7,
    )
    summary = train_anakin_r2d2(cfg, max_frames=10_000)
    assert summary["eval_score_mean"] > 0.3, summary
    assert summary["learn_steps"] > 900
