#!/usr/bin/env python
"""Training entry point (name kept for parity with the reference's
`train_agent_apex.py`, BASELINE.json:5 / SURVEY.md §3.1-3.2).

Roles (--role):
  single   one process: act + learn interleaved (reference's 1-actor mode)
  apex     one process driving the whole device mesh: learner cores + actor
           lanes + sharded replay (the TPU-native Ape-X: the pod IS the
           learner and the actor fleet — no Redis, no external processes)
  anakin   single chip, replay in HBM: the fused sample->learn->write-back
           graph of replay/device.py, zero per-step host transfer (same
           algorithm/schedules as `single`; fastest single-chip learner)

The reference selects learner/actor roles per *process* and couples them
through Redis; here the coupling is XLA collectives + host shared memory, so
both roles live in one SPMD program (SURVEY.md §5 "Distributed communication
backend" mapping).  Pod scale: run the same `--role apex` command on every
host with `--process-count N --process-id i --coordinator-address host0:port`
(docs/RUNBOOK.md "Multi-host Ape-X") — jax.distributed replaces the
reference's remote-actor Redis fabric.
"""

import json
import sys

from rainbow_iqn_apex_tpu.config import parse_config


def main(argv=None) -> int:
    cfg = parse_config(argv)
    if cfg.role != "standby":  # the standby stays jax-free until it takes over
        from rainbow_iqn_apex_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    if cfg.process_count > 1:
        # Pod mode: every host runs this same program (--process-id differs);
        # jax.distributed couples them the way Redis coupled the reference's
        # remote actor processes. Must run BEFORE any jax backend touch.
        from rainbow_iqn_apex_tpu.parallel.multihost import initialize

        initialize(
            cfg.coordinator_address or None, cfg.process_count, cfg.process_id
        )
    if cfg.architecture not in ("iqn", "r2d2"):
        print(
            f"unknown --architecture '{cfg.architecture}' (want 'iqn' or 'r2d2')",
            file=sys.stderr,
        )
        return 2
    if cfg.role == "single" and cfg.architecture == "r2d2":
        from rainbow_iqn_apex_tpu.train_r2d2 import train_r2d2

        summary = train_r2d2(cfg)
    elif cfg.role == "single":
        from rainbow_iqn_apex_tpu.train import train

        summary = train(cfg)
    elif cfg.role == "apex" and cfg.architecture == "r2d2":
        from rainbow_iqn_apex_tpu.parallel.apex_r2d2 import train_apex_r2d2

        summary = train_apex_r2d2(cfg)
    elif cfg.role == "apex":
        from rainbow_iqn_apex_tpu.parallel.apex import train_apex

        summary = train_apex(cfg)
    elif cfg.role == "standby":
        # hot-standby learner (parallel/failover.py; launch_apex.sh
        # --standby): jax-free until it actually claims the learner role,
        # then re-enters the apex entry with --resume auto
        from rainbow_iqn_apex_tpu.parallel.failover import run_standby

        summary = run_standby(cfg)
    elif cfg.role == "anakin" and cfg.architecture == "iqn":
        from rainbow_iqn_apex_tpu.train_anakin import train_anakin

        summary = train_anakin(cfg)
    elif cfg.role == "anakin" and cfg.architecture == "r2d2":
        from rainbow_iqn_apex_tpu.train_anakin_r2d2 import train_anakin_r2d2

        summary = train_anakin_r2d2(cfg)
    else:
        print(
            f"unknown --role '{cfg.role}' (want 'single', 'apex', 'anakin' "
            "or 'standby'; the reference's separate learner/actor processes "
            "are one SPMD program here)",
            file=sys.stderr,
        )
        return 2
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
