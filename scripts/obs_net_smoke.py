#!/usr/bin/env python
"""obs_net_smoke: the live fleet telemetry plane proven end to end,
multi-process (`make obsnet-smoke`; docs/OBSERVABILITY.md "Live fleet
telemetry").

Topology — every hop a REAL socket, every role a real process:

    parent:    the operator — discovers the collector's HTTP surface from
               the `obs_collector` lease alone (the obs_top path), watches
               /fleetz converge, and kills/respawns the collector
    children:  1 obs collector (collector.run_collector: lease epoch
               claimed via next_lease_epoch, addr/port/http_port
               advertised on the lease) and 3 toy trainers (MetricsLogger
               + ObsRelay.attach, discovery via leases ALONE, a tiny
               spool so the outage visibly sheds)

Mid-run the collector is SIGKILLed cold — no goodbye, connections drop,
its lease goes stale — and later respawned: `next_lease_epoch` hands the
new incarnation a bumped epoch, relays re-discover it at its NEW
addr:port, and the fleet view re-converges to ok.

Self-asserted gates (exit 1 on any failure):

  1. the fleet converged pre-kill: /fleetz (found via the lease, never a
     hardcoded URL) shows all 3 trainers, status ok;
  2. training NEVER stalls: every trainer's worst single `logger.log`
     call stays bounded straight through the collector outage (the
     relay's no-stall contract), and every trainer's local JSONL GREW
     during the outage (the wire is the live view, the JSONL is the
     record);
  3. the outage was real and absorbed: relays shed (tiny spool
     overflowed, counted) and every relay reconnected to the respawned
     incarnation;
  4. the fleet re-converged post-restart: the NEW collector's /fleetz
     reaches status ok with all 3 trainers (reconnect flaps degrade one
     fold window, then heal — both edges observed);
  5. the run dir lints as strict schema-versioned JSONL (`obs_net`,
     `alert`, `fleet_health` rows included — the Makefile runs
     lint_jsonl after us).

Usage:
    JAX_PLATFORMS=cpu python scripts/obs_net_smoke.py \\
        --duration 12 --out /tmp/ria_obsnet_smoke
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

# CPU smoke tool: this process and its children (which inherit the pin) never
# ask for an accelerator.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

RUN_ID = "obs_net_smoke"
TRAINERS = 3
COLLECTOR_PID = 99  # lease process id for the collector role


def row(**fields):
    print(json.dumps(fields), flush=True)


def smoke_cfg(out_dir, process_id, collector=False):
    from rainbow_iqn_apex_tpu.config import Config

    kwargs = {}
    if collector:
        kwargs.update(
            obs_net_host="127.0.0.1",  # bind gate: this process IS the
            obs_net_stale_s=2.0,       # collector (ephemeral ports)
            obs_net_tick_s=0.3,
            obs_net_resolution_s=0.2,
        )
    return Config(
        run_id=RUN_ID, results_dir=out_dir, process_id=process_id,
        obs_net=True,
        obs_net_spool=64,        # tiny: the outage must visibly shed
        obs_net_snapshot_s=0.5,
        heartbeat_interval_s=0.25,
        heartbeat_timeout_s=1.5,  # fast lease expiry for the soak
        respawn_base_s=0.05,      # fast relay redial backoff
        respawn_max_s=0.5,
        **kwargs,
    )


def _stop_event_for_child():
    """SIGTERM -> clean stop; orphaned (parent died) -> stop too."""
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    ppid = os.getppid()

    def watchdog():
        while not stop.is_set():
            if os.getppid() != ppid:
                stop.set()
            time.sleep(0.2)

    threading.Thread(target=watchdog, daemon=True).start()
    return stop


# --------------------------------------------------------- collector child
def collector_child(args) -> int:
    """The `obs_collector` role, whole: collector.run_collector claims a
    fresh lease epoch, advertises addr/port/http_port, parks until
    SIGTERM.  A respawn of this child re-runs next_lease_epoch, so the
    new incarnation's lease supersedes the SIGKILLed one's stale file in
    every relay's discovery."""
    from rainbow_iqn_apex_tpu.obs.net.collector import run_collector

    stop = _stop_event_for_child()
    cfg = smoke_cfg(args.out, process_id=COLLECTOR_PID, collector=True)
    run_collector(cfg, stop_event=stop)
    return 0


# ----------------------------------------------------------- trainer child
def trainer_child(args) -> int:
    """One toy trainer: a metrics-cadence learn-row loop with an ObsRelay
    attached THROUGH config + lease discovery (no address plumbed).  The
    loop times every `logger.log` call — the relay's no-stall contract is
    the gate — and writes its ledger (ticks, worst log call, relay
    shed/reconnect stats) for the parent on SIGTERM."""
    from rainbow_iqn_apex_tpu.obs.net.relay import ObsRelay
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    tid = args.trainer_id
    cfg = smoke_cfg(args.out, process_id=tid)
    run_dir = os.path.join(args.out, RUN_ID)
    os.makedirs(run_dir, exist_ok=True)
    logger = MetricsLogger(os.path.join(run_dir, f"trainer{tid}.jsonl"),
                           RUN_ID, echo=False, host=tid)
    registry = MetricRegistry()
    relay = ObsRelay.attach(cfg, logger, registry=registry, role="learner")
    assert relay is not None  # cfg.obs_net is on

    stop = _stop_event_for_child()
    step = 0
    max_log_s = 0.0
    while not stop.is_set():
        step += 1
        registry.counter("frames_total", "trainer").inc(4)
        t0 = time.perf_counter()
        logger.log("learn", step=step, frames=step * 4,
                   loss=1.0 / (1.0 + step))
        max_log_s = max(max_log_s, time.perf_counter() - t0)
        stop.wait(0.004)

    relay.flush(timeout_s=5.0)
    stats = dict(relay.stats(), trainer=tid, ticks=step,
                 max_log_ms=round(max_log_s * 1e3, 3))
    relay.close()
    logger.close()
    path = os.path.join(args.out, f"trainer{tid}_stats.json")
    with open(path + ".tmp", "w") as f:
        json.dump(stats, f)
    os.replace(path + ".tmp", path)
    return 0


# ------------------------------------------------------------------ parent
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=12.0,
                    help="seconds of trainer load (kill + respawn inside)")
    ap.add_argument("--kill-frac", type=float, default=0.35,
                    help="fraction of --duration at which the collector "
                         "is SIGKILLed")
    ap.add_argument("--outage", type=float, default=2.5,
                    help="seconds the collector stays dead")
    ap.add_argument("--boot-timeout", type=float, default=120.0)
    ap.add_argument("--log-stall-bound-ms", type=float, default=1000.0,
                    help="max tolerated single logger.log call")
    ap.add_argument("--out", default="/tmp/ria_obsnet_smoke")
    # internal: child modes
    ap.add_argument("--collector-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--trainer-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--trainer-id", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.collector_child:
        return collector_child(args)
    if args.trainer_child:
        return trainer_child(args)

    from scripts.obs_top import discover_url, fetch_json

    out = args.out
    run_dir = os.path.join(out, RUN_ID)
    hb_dir = os.path.join(run_dir, "heartbeats")
    os.makedirs(hb_dir, exist_ok=True)
    row(event="obs_net_smoke_start", trainers=TRAINERS,
        duration_s=args.duration, out=out)

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    def spawn_collector():
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--collector-child",
             "--out", out],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)

    def spawn_trainer(tid):
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--trainer-child",
             "--trainer-id", str(tid), "--out", out],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)

    collector = spawn_collector()
    trainers = {tid: spawn_trainer(tid) for tid in range(1, TRAINERS + 1)}

    def teardown(rc):
        for proc in [collector] + list(trainers.values()):
            if proc.poll() is None:
                proc.kill()
        return rc

    def fleetz(deadline, want_status=None, want_hosts=TRAINERS):
        """Poll lease-discovered /fleetz until the fleet matches; the
        lease is re-read every poll (the collector may have MOVED)."""
        while time.monotonic() < deadline:
            url = discover_url(out, RUN_ID, timeout_s=1.5)
            fz = fetch_json(url + "/fleetz", timeout_s=2.0) if url else None
            if fz is not None and fz.get("hosts_total", 0) >= want_hosts \
                    and (want_status is None
                         or fz.get("status") == want_status):
                return fz
            time.sleep(0.2)
        return None

    # ---- gate 1: lease-discovered convergence --------------------------
    t0 = time.monotonic()
    pre = fleetz(t0 + args.boot_timeout, want_status="ok")
    converged_pre = pre is not None
    row(event="fleet_converged", pre_kill=converged_pre,
        hosts=(pre or {}).get("hosts_total", 0),
        at_s=round(time.monotonic() - t0, 2))
    if not converged_pre:
        row(path="obs_net_smoke", status="error",
            error="fleet never converged pre-kill")
        return teardown(1)

    # ---- the kill: SIGKILL, no goodbye frame, lease left to rot --------
    kill_at = t0 + args.duration * args.kill_frac
    while time.monotonic() < kill_at:
        time.sleep(0.05)
    jsonl_at_kill = {
        tid: os.path.getsize(os.path.join(run_dir, f"trainer{tid}.jsonl"))
        for tid in trainers}
    collector.kill()
    collector.wait(timeout=10)
    kill_time = time.monotonic()
    row(event="collector_killed", at_s=round(kill_time - t0, 2))

    # ---- the outage: trainers keep logging, relays shed ----------------
    while time.monotonic() < kill_time + args.outage:
        time.sleep(0.05)
    jsonl_after_outage = {
        tid: os.path.getsize(os.path.join(run_dir, f"trainer{tid}.jsonl"))
        for tid in trainers}
    grew_during_outage = all(
        jsonl_after_outage[tid] > jsonl_at_kill[tid] for tid in trainers)
    row(event="outage_over", jsonl_grew=grew_during_outage)

    # ---- the respawn: bumped epoch, new ports, relays re-discover ------
    collector = spawn_collector()
    respawn_time = time.monotonic()
    post = fleetz(respawn_time + args.boot_timeout, want_status="ok")
    reconverged = post is not None
    row(event="fleet_reconverged", post_restart=reconverged,
        hosts=(post or {}).get("hosts_total", 0),
        after_respawn_s=round(time.monotonic() - respawn_time, 2))

    # run out the clock so the post-restart stream carries real load
    while time.monotonic() < t0 + args.duration:
        time.sleep(0.05)
    wall_s = time.monotonic() - t0

    # ---- drain trainers + collect their ledgers ------------------------
    for proc in trainers.values():
        if proc.poll() is None:
            proc.terminate()
    stats = []
    for tid, proc in trainers.items():
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        path = os.path.join(out, f"trainer{tid}_stats.json")
        try:
            with open(path) as f:
                stats.append(json.load(f))
        except OSError:
            row(event="trainer_stats_missing", trainer=tid)
    if collector.poll() is None:
        collector.terminate()
        try:
            collector.wait(timeout=20)
        except subprocess.TimeoutExpired:
            collector.kill()

    total_shed = sum(s.get("shed_rows", 0) for s in stats)
    total_sent = sum(s.get("sent_rows", 0) for s in stats)
    worst_log_ms = max((s.get("max_log_ms", 1e9) for s in stats),
                      default=1e9)
    gates = {
        "converged_pre_kill": converged_pre,
        "never_stalled": len(stats) == TRAINERS
        and worst_log_ms < args.log_stall_bound_ms
        and grew_during_outage,
        "shed_and_reconnected": total_shed > 0
        and all(s.get("reconnects", 0) >= 1 for s in stats),
        "reconverged_post_restart": reconverged,
    }
    result = {
        "path": "obs_net_smoke",
        "metric": "obs_net_smoke_rows_per_sec",
        "value": round(total_sent / max(wall_s, 1e-9), 1),
        "unit": "rows/s",
        "wall_s": round(wall_s, 2),
        "ticks": sum(s.get("ticks", 0) for s in stats),
        "sent_rows": total_sent,
        "shed_rows": total_shed,
        "reconnects": sum(s.get("reconnects", 0) for s in stats),
        "worst_log_ms": round(worst_log_ms, 3),
        "gates": gates,
    }
    if not all(gates.values()):
        result["status"] = "gate_failed"
        row(**result)
        return 1
    row(**result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
