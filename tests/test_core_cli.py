"""The CLI (`train_agent_apex.py --architecture r2d2 --core-config <file>`)
with every non-LSTM core, at tiny widths: the host-fed anakin loop,
`train_r2d2` and the apex R2D2 driver (the fused trainer's cases are
tests/test_core_cli_fused.py's, which reads `CORES` from here).  Files of
their own: these are the slowest cases of the cores' tests, the suite runs a
file a worker, and with five cores one file of both was its longest worker by
itself."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CORES = {name: os.path.join(HERE, "fixtures", name + "_core_tiny.json")
         for name in ("kimi", "deepseek_v3", "qwen3_next", "ouro", "lfm2")}


@pytest.mark.parametrize("core", sorted(CORES))
@pytest.mark.parametrize("role,learners", [
    ("anakin", 1), ("single", 1), ("apex", 4)])
def test_host_fed_roles_train_with_the_core(tmp_path, role, learners, core):
    """The host-fed anakin loop, `train_r2d2` and the apex R2D2 driver carry
    the core's state pytree per lane and a ring without stored state."""
    import train_agent_apex

    rc = train_agent_apex.main([
        "--role", role, "--architecture", "r2d2", "--env-id", "toy:catch",
        "--core-config", CORES[core], "--compute-dtype", "float32",
        "--history-length", "2", "--hidden-size", "32",
        "--r2d2-burn-in", "2", "--r2d2-seq-len", "6", "--r2d2-overlap", "4",
        "--batch-size", "4", "--multi-step", "2", "--memory-capacity", "800",
        "--learn-start", "64", "--frames-per-learn", "2",
        "--num-envs-per-actor", "4", "--anakin-segment-ticks", "8",
        "--learner-devices", str(learners), "--eval-episodes", "1",
        "--eval-interval", "0", "--checkpoint-interval", "0",
        "--metrics-interval", "1", "--t-max", "160", "--run-id", role,
        "--results-dir", str(tmp_path / "results"),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert rc == 0
    rows = [json.loads(line) for line in open(
        tmp_path / "results" / role / "metrics.jsonl")]
    # a row logged before its step's loss came back carries null
    losses = [r["loss"] for r in rows
              if r["kind"] == "learn" and r["loss"] is not None]
    assert losses and all(np.isfinite(x) for x in losses)
