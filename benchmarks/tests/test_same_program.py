"""The driver runs the trainer's program and no lookalike: from one seed, after
k dispatches, the driver's `ts.step` and per-dispatch mean loss equal the
`learn_steps` and the `learn` rows' loss of `train_anakin_r2d2` bit for bit.
The trainer's initialiser and its empty ring are replaced by the benchmark's
seeded weights and seeded ring; everything else is the trainer's own."""

import json

import jax
import numpy as np
import pytest

from benchmarks.tests import tiny

SEGMENTS = 8


def _trainer_rows(trainer, cfg, tmp_path):
    summary = trainer(cfg, max_frames=SEGMENTS * cfg.anakin_segment_ticks
                      * cfg.num_envs_per_actor)
    rows = [json.loads(line) for line in
            open(tmp_path / "results" / cfg.run_id / "metrics.jsonl")]
    return summary, [r["loss"] for r in rows if r.get("kind") == "learn"]


@pytest.mark.parametrize("lanes", [4, 8])
def test_driver_is_the_trainers_program(lanes, tmp_path, monkeypatch):
    from rainbow_iqn_apex_tpu import train_anakin_r2d2
    from rainbow_iqn_apex_tpu.replay import device_sequence

    from benchmarks.drivers.fused_r2d2 import Driver

    fields = tiny.r2d2_fields()
    traffic = tiny.traffic("freeway-16lanes", lanes=lanes)
    trainer = train_anakin_r2d2.train_anakin_r2d2
    drv = Driver(fields, traffic, 2**31 + 11, 1)
    ts0, ss0 = jax.tree.map(np.asarray, drv.carry[:2])
    steps, losses = 0, []
    for _ in range(SEGMENTS):
        steps, outs, _k = drv.dispatch()
        loss = np.asarray(outs[1])
        if np.any(np.isfinite(loss)):
            losses.append(float(np.nanmean(loss)))

    # the trainer, on the benchmark's weights and seeded ring, logging every
    # learning dispatch
    monkeypatch.setattr(train_anakin_r2d2, "init_r2d2_state",
                        lambda *a, **k: jax.tree.map(jax.numpy.asarray, ts0))
    monkeypatch.setattr(device_sequence.DeviceSequenceReplay, "init_state",
                        lambda self: jax.tree.map(jax.numpy.asarray, ss0))
    cfg = drv.cfg.replace(
        results_dir=str(tmp_path / "results"),
        checkpoint_dir=str(tmp_path / "checkpoints"),
        metrics_interval=1, eval_episodes=1, eval_interval=0,
        checkpoint_interval=0)
    summary, rows = _trainer_rows(trainer, cfg, tmp_path)
    assert steps > 0 and summary["learn_steps"] == steps
    assert rows == losses
