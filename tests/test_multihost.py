"""Multi-host execution: 2-process jax.distributed over a CPU Gloo fabric.

SURVEY §2 rows 6-7 (the reference's remote Redis actors) + §5 backend
mapping: each host contributes local env lanes / replay shards / sub-batches
to one SPMD program; the only cross-host traffic is the collectives XLA
inserts.  These tests spawn two REAL processes (2 local CPU devices each,
4 global) and check (a) dp-sharded learn numerics match a single-process run
of the same global batch, and (b) the full train_apex loop runs end-to-end
multi-host.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_CHILD = os.path.join(os.path.dirname(__file__), "_multihost_child.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_pair(mode: str, *extra: str, timeout: float = 420.0):
    """Run the child program as 2 coupled jax.distributed processes.

    Children write to temp FILES, not pipes — a chatty child blocked on a
    full pipe buffer would stall the shared collective and hang both.  On
    timeout BOTH children are killed (a wedged pair must not leak past the
    test holding its port)."""
    import tempfile

    port = str(_free_port())
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as td:
        files = [open(os.path.join(td, f"out{pid}.log"), "w+") for pid in (0, 1)]
        procs = [
            subprocess.Popen(
                [sys.executable, _CHILD, mode, str(pid), port, *extra],
                env=env, stdout=files[pid], stderr=subprocess.STDOUT, text=True,
            )
            for pid in (0, 1)
        ]
        try:
            deadline = __import__("time").monotonic() + timeout
            for p in procs:
                p.wait(timeout=max(deadline - __import__("time").monotonic(), 1))
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            for p in procs:
                p.wait()
            raise
        outs = []
        for f in files:
            f.seek(0)
            outs.append(f.read())
            f.close()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child rc={p.returncode}\n{out[-4000:]}"
    for line in reversed(outs[0].strip().splitlines()):
        try:
            return json.loads(line)
        except (ValueError, json.JSONDecodeError):
            continue
    raise AssertionError(f"no JSON from process 0:\n{outs[0][-4000:]}")


@pytest.mark.slow
def test_two_process_learn_matches_single_process():
    """3 learn steps over a 2-process dp mesh == the same steps single-
    process on the full batch (same config/seed => same init and keys)."""
    result = _spawn_pair("learn")

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.parallel.apex import ApexDriver
    from tests._multihost_child import fixed_global_batch

    cfg = Config(
        compute_dtype="float32", frame_height=44, frame_width=44,
        history_length=2, hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=2,
        batch_size=8, learner_devices=0,
    )
    A = 4
    driver = ApexDriver(cfg, A)
    full = fixed_global_batch(cfg, A, cfg.batch_size)
    # replicate the multi-host global IS-weight derivation exactly:
    # q(i) = prob_local(i) / n_hosts, w = (N q)^-beta, max-normalized
    import dataclasses

    q = np.asarray(full.prob) / 2
    w = (100 * np.maximum(q, 1e-12)) ** (-0.6)
    full = dataclasses.replace(full, weight=(w / w.max()).astype(np.float32))
    losses, pri = [], None
    for _ in range(3):
        info = driver.learn(full)
        losses.append(float(info["loss"]))
        pri = np.asarray(info["priorities"])

    np.testing.assert_allclose(result["losses"], losses, rtol=2e-4, atol=2e-5)
    # process 0 held global rows [0, B/2): its local priorities must be the
    # first half of the single-process ones
    np.testing.assert_allclose(
        result["local_priorities"], pri[: cfg.batch_size // 2],
        rtol=2e-3, atol=2e-4,
    )
    checksum = float(
        sum(float(np.abs(np.asarray(p)).sum())
            for p in __import__("jax").tree.leaves(driver.state.params))
    )
    np.testing.assert_allclose(result["checksum"], checksum, rtol=1e-5)


@pytest.mark.slow
def test_two_process_r2d2_learn_matches_single_process():
    """The recurrent learn step under the same 2-process topology: losses,
    local priority rows and the param checksum must match a single-process
    run of the same global sequence batch."""
    result = _spawn_pair("r2d2-learn")

    import dataclasses

    import jax

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.r2d2 import to_device_seq_batch
    from rainbow_iqn_apex_tpu.parallel.apex_r2d2 import R2D2ApexDriver
    from tests._multihost_child import main as _  # noqa: F401 (import check)
    from rainbow_iqn_apex_tpu.replay.sequence import SequenceSample  # noqa: F401

    cfg = Config(
        compute_dtype="float32", history_length=1, hidden_size=32,
        lstm_size=32, r2d2_burn_in=2, r2d2_seq_len=6, r2d2_overlap=2,
        multi_step=2, gamma=0.9, batch_size=8, learner_devices=0,
    )
    A, B, FRAME = 3, cfg.batch_size, (44, 44)
    L = cfg.r2d2_burn_in + cfg.r2d2_seq_len
    driver = R2D2ApexDriver(cfg, A, FRAME, lanes=8)
    rng = np.random.default_rng(0)
    full = SequenceSample(
        idx=np.arange(B),
        obs=rng.integers(0, 255, (B, L, *FRAME, 1), dtype=np.uint8),
        action=rng.integers(0, A, (B, L)).astype(np.int32),
        reward=rng.normal(size=(B, L)).astype(np.float32),
        done=np.zeros((B, L), bool),
        valid=np.ones((B, L), bool),
        init_c=np.zeros((B, 32), np.float32),
        init_h=np.zeros((B, 32), np.float32),
        weight=np.ones(B, np.float32),
        prob=(rng.random(B) + 0.1).astype(np.float64),
    )
    # the multi-host global IS-weight derivation, replicated exactly
    q = np.asarray(full.prob) / 2
    w = (50 * np.maximum(q, 1e-12)) ** (-0.6)
    full = dataclasses.replace(full, weight=(w / w.max()).astype(np.float32))
    losses, pri = [], None
    for _ in range(3):
        info = driver.learn_batch(to_device_seq_batch(full))
        losses.append(float(info["loss"]))
        pri = np.asarray(info["priorities"])

    np.testing.assert_allclose(result["losses"], losses, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        result["local_priorities"], pri[: B // 2], rtol=2e-3, atol=2e-4
    )
    checksum = float(
        sum(float(np.abs(np.asarray(p)).sum())
            for p in jax.tree.leaves(driver.state.params))
    )
    np.testing.assert_allclose(result["checksum"], checksum, rtol=1e-5)


@pytest.mark.slow
def test_two_process_train_apex_end_to_end(tmp_path):
    summary = _spawn_pair("train", str(tmp_path))
    assert summary["frames"] == 800
    assert summary["learn_steps"] > 0
    assert summary["lanes"] == 8
    assert np.isfinite(summary["eval_score_mean"])


@pytest.mark.slow
def test_two_process_r2d2_train_end_to_end(tmp_path):
    summary = _spawn_pair("r2d2-train", str(tmp_path))
    assert summary["frames"] == 800
    assert summary["learn_steps"] > 0
    assert summary["lanes"] == 8
    assert np.isfinite(summary["eval_score_mean"])


# ---------------------------------------------------- lease-monitor edges
# (PR 4 bugfix satellite; fast — no child processes, pure file logic)
def _stale_write(path, payload, age_s=5.0):
    import time as _time

    with open(path, "w") as f:
        json.dump(payload, f)
    old = _time.time() - age_s
    os.utime(path, (old, old))


def test_monitor_does_not_refire_host_dead_after_file_gap(tmp_path):
    """Regression: the monitor used to forget a reported host the moment its
    file became unobservable (eviction cleanup, a torn read racing a
    rename), so a lingering stale file re-emitted host_dead on every poll
    after such a gap.  Dead reports must persist until the host is observed
    ALIVE — once per lease epoch, not once per filesystem glitch."""
    from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatMonitor

    hb = tmp_path / "hb"
    hb.mkdir()
    path = str(hb / "h1.json")
    _stale_write(path, {"process_id": 1, "epoch": 0})
    monitor = HeartbeatMonitor(str(hb), timeout_s=0.5)
    assert monitor.newly_dead() == [1]
    assert monitor.newly_dead() == []  # steady stale: edge fired once
    os.remove(path)  # eviction cleanup: the file vanishes...
    assert monitor.newly_dead() == []
    # ...and a lingering stale copy of the SAME epoch reappears (NFS cache,
    # a laggard flush from the dead incarnation).  The old code refired
    # host_dead here on every poll cycle.
    _stale_write(path, {"process_id": 1, "epoch": 0})
    assert monitor.newly_dead() == []
    assert monitor.newly_dead() == []
    # a NEW incarnation that died before ever beating fresh IS a new death
    _stale_write(path, {"process_id": 1, "epoch": 1})
    assert monitor.newly_dead() == [1]
    assert monitor.newly_dead() == []


def test_monitor_reports_host_alive_edge_with_lease_payload(tmp_path):
    """A recovered host is detected, not just a dead one: a fresh beat from
    a reported-dead host fires host_alive exactly once, carrying the lease
    payload (role/shard/epoch/weight_version) the readmission path needs."""
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatMonitor,
        HeartbeatWriter,
    )
    from rainbow_iqn_apex_tpu.utils import faults

    hb = tmp_path / "hb"
    hb.mkdir()
    _stale_write(str(hb / "h2.json"), {"process_id": 2, "epoch": 0})
    monitor = HeartbeatMonitor(str(hb), timeout_s=0.5)
    dead, alive = monitor.poll()
    assert [lease.host for lease in dead] == [2] and alive == []
    # the respawned incarnation leases back in at epoch 1
    writer = HeartbeatWriter(str(hb), 2, 0.05,
                             injector=faults.FaultInjector(""),
                             role="actor", shard=1, epoch=1)
    writer.set_weight_version(7)
    writer.beat()
    dead, alive = monitor.poll()
    assert dead == [] and len(alive) == 1
    lease = alive[0]
    assert (lease.host, lease.epoch, lease.role, lease.shard,
            lease.weight_version) == (2, 1, "actor", 1, 7)
    assert monitor.poll() == ([], [])  # alive edge fired once
