#!/usr/bin/env python
"""league_soak: drive a REAL 2-member PBT population end to end and assert
the exploit/explore loop from its own JSONL (docs/LEAGUE.md).

    python scripts/league_soak.py --out /tmp/league --t-max 4096
    python scripts/league_soak.py --members 2 --json

Topology (the chaos_soak shape, with REAL trainers as the children):

    parent = LeagueController (jax-free)        member children (one per
      RoleSupervisor (respawn keeps member id)    member id, REAL train()
      fitness from tailed eval rows        <---   loops on toy:catch with
      forced truncation exploit sweep             league wiring live)
      winner outbox chain --copy--> loser inbox + directive
                                           --->  drain-boundary adoption
                                                 (digest-asserted)

Each member child runs the genuine single-process training loop
(`rainbow_iqn_apex_tpu.train.train`) at toy scale with
``league_member_id``/``league_dir`` set: genome overlay at loop start,
int8-delta outbox publishes at the weight-publish cadence, exploit
directive polls at drain boundaries, live lr/n-step/omega adoption — the
exact code path a real league member runs, not a mock.

The harness asserts (exit 0 only if ALL hold):
  * >= 1 exploit event fired (forced once both members have fitness);
  * the loser's adoption is BIT-EXACT: its `league` adopt row's digest
    equals the directive digest the controller computed from the winner's
    published outbox reconstruction;
  * the loser's adopted genome differs from the winner's (explore really
    perturbed it);
  * member leases in league_dir/heartbeats carried member/generation
    payloads (the lease contract, parallel/elastic.py);
  * a final `league` status row exists and the population never collapsed;
  * every JSONL under the league dir lints against the obs/ schema.

`make league-smoke` runs this after the league-marked tier-1 tests.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


# ---------------------------------------------------------------- member child
def member_main(args) -> int:
    """One REAL league member: the single-process train loop at toy scale."""
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.train import train

    mdir = os.path.join(args.dir, f"m{args.member_id}")
    cfg = Config(
        run_id=f"member{args.member_id}",
        seed=args.seed + 31 * args.member_id,
        results_dir=os.path.join(mdir, "results"),
        checkpoint_dir=os.path.join(mdir, "ckpt"),
        env_id="toy:catch",
        compute_dtype="float32",
        history_length=2,
        frame_height=10, frame_width=10,  # toy:catch defines its own shape
        hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, learning_rate=1e-3, multi_step=3, gamma=0.9,
        memory_capacity=4096, learn_start=256, frames_per_learn=2,
        target_update_period=200, num_envs_per_actor=8,
        metrics_interval=50, eval_interval=args.eval_interval,
        checkpoint_interval=0, guard_snapshot_interval=500,
        eval_episodes=2, t_max=args.t_max,
        weight_publish_interval=args.publish_interval,
        heartbeat_interval_s=0.2,
        league_dir=args.dir,
        league_member_id=args.member_id,
    )
    train(cfg)
    return 0


# ------------------------------------------------------------------ controller
def soak_main(args) -> int:
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.league.controller import LeagueController
    from rainbow_iqn_apex_tpu.league.member import EPOCH_ENV
    from rainbow_iqn_apex_tpu.obs.health import RunHealth
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatMonitor
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    league_dir = os.path.abspath(args.out)
    os.makedirs(league_dir, exist_ok=True)
    cfg = Config(
        run_id=f"league_{args.seed}",
        seed=args.seed,
        # the controller's config is the population's BASELINE genome
        # (member 0 keeps it; the rest perturb around it) — match the
        # members' toy-scale tuning, not the Atari defaults
        learning_rate=1e-3, multi_step=3, priority_exponent=0.5,
        league_dir=league_dir,
        league_population=args.members,
        league_fitness_window=2,
        league_exploit_interval_s=1e9,  # sweeps fire only when FORCED —
        # the soak's one exploit event is deterministic, not timer-raced
        league_bottom_quantile=0.5,
        league_top_quantile=0.5,
        league_perturb_factor=1.3,
        league_resample_prob=0.0,  # the perturbed-not-equal gate must not
        # depend on which explore branch the rng took
    )
    metrics = MetricsLogger(
        os.path.join(league_dir, "controller", "metrics.jsonl"),
        run_id=cfg.run_id, echo=not args.quiet, host=0)
    registry = MetricRegistry()
    health = RunHealth(registry, metrics, role="league")
    metrics.add_observer(health.observe_row)

    def spawn_member(member_id: int, epoch: int):
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        env[EPOCH_ENV] = str(epoch)
        # the soak exercises league plumbing, not accelerators
        env.setdefault("JAX_PLATFORMS", "cpu")
        argv = [
            sys.executable, os.path.abspath(__file__), "--member",
            "--member-id", str(member_id), "--dir", league_dir,
            "--seed", str(args.seed), "--t-max", str(args.t_max),
            "--eval-interval", str(args.eval_interval),
            "--publish-interval", str(args.publish_interval),
        ]
        log = open(os.path.join(
            league_dir, f"member{member_id}_e{epoch}.log"), "ab")
        return subprocess.Popen(argv, env=env, stdout=log,
                                stderr=subprocess.STDOUT)

    ctl = LeagueController(cfg, spawn_member, metrics=metrics,
                           registry=registry)
    monitor = HeartbeatMonitor(
        os.path.join(league_dir, "heartbeats"), timeout_s=5.0)

    exploits: list = []
    lease_with_member = False
    deadline = time.monotonic() + args.deadline_s
    step = 0
    last_status = {}
    try:
        while time.monotonic() < deadline:
            step += 1
            ctl.poll(step=step)
            for lease in monitor.leases().values():
                if lease.member is not None and lease.generation >= 0:
                    lease_with_member = True
            scored = [m for m in ctl.alive_members()
                      if ctl.fitness.fitness(m) is not None]
            if not exploits and len(scored) >= 2:
                # both members measured: force the one seeded exploit
                # sweep (re-forced next tick if a publish race skipped it)
                exploits = ctl.force_sweep(step=step)
            if step % 20 == 0:
                last_status = ctl.status_row(step=step)
                health.tick(step)
            if exploits and _adoptions(league_dir):
                break  # story complete: exploit fired AND the loser adopted
            time.sleep(args.tick_s)
        last_status = ctl.status_row(step=step)
        health.tick(step + 1)
    finally:
        ctl.stop_all()
        metrics.close()

    # ----------------------------------------------------- harness assertions
    failures = []
    if not exploits:
        failures.append("no exploit event fired before the deadline")
    adopts = _adoptions(league_dir)
    if not adopts:
        failures.append("no member ever adopted (no `league` adopt row)")
    for directive in exploits:
        loser = directive["member"]
        match = [a for a in adopts if a.get("member") == loser
                 and a.get("generation") == directive["generation"]]
        if not match:
            failures.append(
                f"member m{loser} never adopted generation "
                f"{directive['generation']}")
            continue
        adopt = match[0]
        if adopt.get("digest") != directive["digest"]:
            failures.append(
                f"m{loser} adoption digest {adopt.get('digest')!r} != "
                f"directive {directive['digest']!r} — the bit-exact copy "
                "contract broke")
        winner_genome = last_status.get("members", {}).get(
            str(directive["source"]), {})
        if (directive["genome"].get("learning_rate")
                == winner_genome.get("lr")):
            failures.append(
                f"m{loser}'s adopted genome kept the source's learning "
                "rate — explore never perturbed it")
    if not lease_with_member:
        failures.append("no member lease carried member/generation payload")
    if not last_status.get("members"):
        failures.append("no final league status row")
    if last_status.get("collapsed"):
        failures.append("population collapsed")

    # every JSONL under the league dir must lint against the obs schema
    from scripts.lint_jsonl import lint_file  # noqa: E402

    lint_errors = []
    for path in sorted(glob.glob(os.path.join(league_dir, "**", "*.jsonl"),
                                 recursive=True)):
        lint_errors += lint_file(path)
    if lint_errors:
        failures.append(f"lint errors: {lint_errors[:5]}")

    summary = {
        "ok": not failures,
        "exploits": len(exploits),
        "adoptions": len(adopts),
        "members": {k: {"fitness": v.get("fitness"),
                        "generation": v.get("generation"),
                        "restarts": v.get("restarts")}
                    for k, v in (last_status.get("members") or {}).items()},
        "failures": failures,
    }
    with open(os.path.join(league_dir, "soak_summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    print(json.dumps(summary, indent=2) if args.json else (
        f"league_soak: {'OK' if summary['ok'] else 'FAILED'} "
        f"exploits={summary['exploits']} adoptions={summary['adoptions']}"
        + "".join(f"\n  FAIL {f}" for f in failures)))
    return 0 if summary["ok"] else 1


def _adoptions(league_dir: str) -> list:
    """Every `league` adopt row any member has written so far."""
    out = []
    for path in glob.glob(os.path.join(league_dir, "m*", "**", "*.jsonl"),
                          recursive=True):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue
                    if (row.get("kind") == "league"
                            and row.get("event") == "adopt"):
                        out.append(row)
        except OSError:
            continue
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--members", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="/tmp/ria_league_soak")
    ap.add_argument("--t-max", type=int, default=6144,
                    help="env frames per member trainer (toy scale)")
    ap.add_argument("--eval-interval", type=int, default=150)
    ap.add_argument("--publish-interval", type=int, default=100)
    ap.add_argument("--deadline-s", type=float, default=300.0)
    ap.add_argument("--tick-s", type=float, default=0.25)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--quiet", action="store_true")
    # internal: member-child mode
    ap.add_argument("--member", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--member-id", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--dir", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.member:
        return member_main(args)
    return soak_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
