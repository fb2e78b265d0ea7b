#!/usr/bin/env python
"""replay_net_smoke: the cross-host replay plane proven end to end,
multi-process (`make replaynet-smoke`; docs/RESILIENCE.md "replay plane").

Topology — every hop a REAL socket, every role a real process:

    parent:   the learner — a RemoteReplayPlane discovering the shard
              servers purely from lease files, pipelining SampleClient
              batches, writing priorities back, requesting a server-side
              snapshot fenced by its own step
    children: 2 replay shard servers (each owning one ShardedReplay shard
              block, advertising addr:port + shard range + epoch through
              its lease) and 2 actor hosts (each a RemoteReplayPlane in
              append-only mode, spooling lockstep lane ticks)

Mid-load one shard server is SIGKILLed cold — no goodbye frame,
connections drop, its lease expires — and later respawned at the SAME
shard base: `next_lease_epoch` hands the incarnation a bumped epoch, the
server restores its own snapshot, and the plane readmits it epoch-fenced.

Self-asserted gates (exit 1 on any failure):

  1. the learner and both actors discovered both servers via leases alone;
  2. the learner NEVER stalls: no `get()` timeout, and the worst
     inter-batch gap stays bounded straight through the kill
     (survivors-only full batches);
  3. ZERO appended-and-acked transitions lost on survivors: the surviving
     server's wire-reported ``rows_appended`` covers every row the actors
     counted as acked to it (at-least-once append: re-spooled blocks may
     duplicate, never vanish);
  4. readmit restores sampling from the REVIVED incarnation: post-respawn
     batches draw global indices from the victim's shard range again;
  5. the pre-kill server-side snapshot was acked by every server (the
     learner-step fence exercised over the wire);
  6. the run dir lints as strict schema-versioned JSONL (replay_net rows
     included — the Makefile runs lint_jsonl after us).

Usage:
    JAX_PLATFORMS=cpu python scripts/replay_net_smoke.py \\
        --duration 12 --out /tmp/ria_replaynet_smoke
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

RUN_ID = "replay_net_smoke"
FRAME = (12, 12)
SERVERS = 2          # one shard block each
LANES_PER_SHARD = 2  # actor lanes_total = SERVERS * LANES_PER_SHARD
CAPACITY = 2048      # per server (== per shard: 1 shard per server)


def row(**fields):
    print(json.dumps(fields), flush=True)


def smoke_cfg(out_dir, process_id, seed=0):
    from rainbow_iqn_apex_tpu.config import Config

    return Config(
        run_id=RUN_ID, seed=seed, results_dir=out_dir,
        process_id=process_id,
        replay_shards=SERVERS,       # global shard blocks == servers here
        heartbeat_timeout_s=1.5,     # fast lease expiry for the soak
        replay_net_remote=True,
    )


def _lanes_total() -> int:
    return SERVERS * LANES_PER_SHARD


def _stop_event_for_child():
    """SIGTERM -> clean stop; orphaned (parent died) -> stop too."""
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    return stop


# ------------------------------------------------------------- server child
def server_child(args) -> int:
    """One replay shard server: ShardedReplay block + ReplayShardServer +
    lease with addr:port/shard range/epoch.  `next_lease_epoch` claims the
    incarnation epoch, so a respawn of the same server id automatically
    registers with a bumped epoch (the fence stale clients trip).  The
    snapshot prefix is stable per server id: a respawned incarnation
    restores what its predecessor snapshotted, fenced by the learner step
    recorded alongside."""
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatWriter,
        next_lease_epoch,
    )
    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
    from rainbow_iqn_apex_tpu.replay.net.server import ReplayShardServer
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    sid = args.server_id
    epoch = next_lease_epoch(args.hb_dir, sid)
    memory = ShardedReplay.build(
        1, CAPACITY, LANES_PER_SHARD, frame_shape=FRAME, history=2,
        n_step=3, gamma=0.9, seed=args.seed + 100 * sid)
    logger = MetricsLogger(
        os.path.join(args.out, f"server{sid}.e{epoch}.jsonl"),
        run_id=RUN_ID, echo=False, host=sid)
    srv = ReplayShardServer(
        memory, shard_base=args.shard_base, host="127.0.0.1", port=0,
        epoch=epoch,
        snapshot_prefix=os.path.join(args.out, f"replay_shard{sid}"),
        logger=logger).start()
    writer = HeartbeatWriter(args.hb_dir, sid, interval_s=0.25,
                             role="replay_shard", shard=args.shard_base,
                             epoch=epoch)
    srv.attach_lease(writer)  # addr:port + shard range BEFORE the first beat
    writer.start()

    stop = _stop_event_for_child()
    ppid = os.getppid()
    while not stop.is_set():
        if os.getppid() != ppid:  # orphaned: the parent died, so should we
            break
        stop.wait(0.2)
    writer.stop()
    srv.stop()
    logger.close()
    return 0


# -------------------------------------------------------------- actor child
def actor_child(args) -> int:
    """One actor host: a RemoteReplayPlane in append-only mode spooling
    lockstep lane ticks across both servers.  `poll()` drives its own
    discovery/readmit lifecycle, so appends to the killed server spool
    locally and land on the revived incarnation.  On SIGTERM it flushes
    every appender and writes its acked-rows accounting for the parent's
    zero-loss gate."""
    import numpy as np

    from rainbow_iqn_apex_tpu.replay.net.plane import RemoteReplayPlane
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    aid = args.actor_id
    cfg = smoke_cfg(args.out, process_id=10 + aid, seed=args.seed)
    logger = MetricsLogger(os.path.join(args.out, f"actor{aid}.jsonl"),
                           run_id=RUN_ID, echo=False, host=10 + aid)
    plane = RemoteReplayPlane(cfg, _lanes_total(), metrics=logger)
    rng = np.random.default_rng(args.seed + 7 * aid)
    stop = _stop_event_for_child()
    ppid = os.getppid()

    # wait for both servers' leases before appending (bounded): appends to
    # an undiscovered owner shed by design, but a cold-start shed storm
    # would only add noise to the loss accounting
    deadline = time.monotonic() + args.boot_timeout
    while (len(plane.peers) < SERVERS and not stop.is_set()
           and time.monotonic() < deadline):
        plane.poll(0)
        time.sleep(0.1)

    lanes = _lanes_total()
    tick = 0
    while not stop.is_set():
        if os.getppid() != ppid:
            break
        rewards = rng.normal(size=lanes).astype(np.float32)
        plane.append_batch(
            rng.integers(0, 255, (lanes, *FRAME), dtype=np.uint8),
            rng.integers(0, 4, lanes),
            rewards,
            rng.random(lanes) < 0.02,
            priorities=np.abs(rewards) + 0.05,
        )
        tick += 1
        if tick % 50 == 0:
            plane.poll(tick)  # lease edges: drop / epoch-fenced readmit
        time.sleep(0.004)

    # drain, then account: acked_rows per server is the parent's zero-loss
    # ledger (only rows the server ACKED count — shed/spooled don't)
    for ac in plane._appenders.values():
        ac.flush(timeout_s=10.0)
    stats = {
        "actor": aid,
        "ticks": tick,
        "shed_lanes": plane.shed_lanes,
        "acked_by_server": {
            str(pid): ac.acked_rows for pid, ac in plane._appenders.items()
        },
        "fenced_by_server": {
            str(pid): ac.fenced_rows for pid, ac in plane._appenders.items()
        },
        "shed_ticks": sum(ac.shed_ticks for ac in plane._appenders.values()),
    }
    path = os.path.join(args.out, f"actor{aid}_stats.json")
    with open(path + ".tmp", "w") as f:
        json.dump(stats, f)
    os.replace(path + ".tmp", path)
    plane.close()
    logger.close()
    return 0


# ------------------------------------------------------------------ parent
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=12.0,
                    help="seconds of sampling load (kill + respawn inside)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--kill-frac", type=float, default=0.4,
                    help="fraction of --duration at which a server is killed")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--boot-timeout", type=float, default=120.0)
    ap.add_argument("--stall-bound", type=float, default=10.0,
                    help="max tolerated gap between batches, seconds")
    ap.add_argument("--out", default="/tmp/ria_replaynet_smoke")
    # internal: child modes
    ap.add_argument("--server-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--actor-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--server-id", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--shard-base", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--actor-id", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--hb-dir", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.server_child:
        return server_child(args)
    if args.actor_child:
        return actor_child(args)

    import numpy as np

    from rainbow_iqn_apex_tpu.replay.net.plane import RemoteReplayPlane
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    out = args.out
    os.makedirs(out, exist_ok=True)
    hb_dir = os.path.join(out, RUN_ID, "heartbeats")
    row(event="replay_net_smoke_start", servers=SERVERS, actors=2,
        duration_s=args.duration, out=out)

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    def spawn_server(sid):
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--server-child",
             "--server-id", str(sid), "--shard-base", str(sid - 1),
             "--hb-dir", hb_dir, "--out", out, "--seed", str(args.seed),
             "--boot-timeout", str(args.boot_timeout)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)

    def spawn_actor(aid):
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--actor-child",
             "--actor-id", str(aid), "--hb-dir", hb_dir, "--out", out,
             "--seed", str(args.seed),
             "--boot-timeout", str(args.boot_timeout)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)

    servers = {sid: spawn_server(sid) for sid in range(1, SERVERS + 1)}
    actors = {aid: spawn_actor(aid) for aid in range(1, 3)}

    def teardown(rc):
        for proc in list(servers.values()) + list(actors.values()):
            if proc.poll() is None:
                proc.kill()
        return rc

    # ---- the learner: discovery via leases alone, then pipelined sampling
    cfg = smoke_cfg(out, process_id=0, seed=args.seed)
    metrics = MetricsLogger(os.path.join(out, "learner.jsonl"),
                            run_id=RUN_ID, echo=False, host=0)
    plane = RemoteReplayPlane(cfg, _lanes_total(), metrics=metrics)
    warm_rows = 4 * args.batch * SERVERS
    deadline = time.monotonic() + args.boot_timeout
    while time.monotonic() < deadline:
        plane.poll(0)
        if (len(plane.peers) == SERVERS and plane.size() >= warm_rows
                and plane.sampleable()):
            break
        time.sleep(0.2)
    discovered_peers = len(plane.peers)
    row(event="replay_discovered", peers=discovered_peers,
        rows=plane.size())
    if discovered_peers != SERVERS or plane.size() < warm_rows:
        row(path="replay_net_smoke", status="error",
            error=f"boot incomplete: peers={len(plane.peers)} "
                  f"rows={plane.size()}")
        return teardown(1)

    sc = plane.start_sampling(args.batch, lambda: 0.5)
    victim = 1  # owns shard_base 0: global slots [0, CAPACITY)
    victim_lo, victim_hi = 0, CAPACITY

    t0 = time.monotonic()
    kill_at = t0 + args.duration * args.kill_frac
    snapshot_at = t0 + args.duration * 0.25
    hard_stop = t0 + args.duration * 4 + 60.0
    killed = respawned = False
    snapshot_acked = -1
    readmit_seen = revived_seen = False
    batches = 0
    timeouts = 0
    max_gap = 0.0
    last_batch = time.monotonic()
    kill_time = respawn_time = 0.0
    step = 0

    while True:
        now = time.monotonic()
        if now >= hard_stop:
            break
        if now >= t0 + args.duration and revived_seen:
            break
        step += 1
        try:
            s = sc.get(timeout=args.stall_bound * 2)
        except TimeoutError:
            timeouts += 1
            row(event="learner_get_timeout", at_s=round(now - t0, 2))
            continue
        got = time.monotonic()
        max_gap = max(max_gap, got - last_batch)
        last_batch = got
        batches += 1
        if (respawned and readmit_seen and not revived_seen
                and bool(np.any((s.idx >= victim_lo) & (s.idx < victim_hi)))):
            revived_seen = True
            row(event="revived_range_sampled", at_s=round(got - t0, 2),
                after_respawn_s=round(got - respawn_time, 2))
        sc.update_priorities(s.idx, np.abs(s.reward) + 0.01)
        if batches % 32 == 0:
            plane.flush_writebacks()
        plane.poll(step)
        if snapshot_acked < 0 and now >= snapshot_at:
            snapshot_acked = plane.request_snapshot(step)
            row(event="snapshot_requested", acked=snapshot_acked, step=step)
        if not killed and now >= kill_at:
            servers[victim].kill()  # SIGKILL: no goodbye frame, no drain
            killed = True
            kill_time = now
            row(event="server_killed", server=victim,
                at_s=round(now - t0, 2))
        if (killed and not respawned
                and (victim in sc.dead_peers()
                     or now >= kill_time + 6.0)):
            servers[victim] = spawn_server(victim)
            respawned = True
            respawn_time = time.monotonic()
            row(event="server_respawned", server=victim,
                dropped_first=victim in sc.dead_peers(),
                at_s=round(respawn_time - t0, 2))
        if respawned and not readmit_seen and victim not in sc.dead_peers():
            readmit_seen = True
            row(event="server_readmitted", server=victim,
                at_s=round(time.monotonic() - t0, 2))
        time.sleep(0.005)
    wall_s = time.monotonic() - t0
    plane.flush_writebacks()

    # ---- actors drain + write their acked ledgers ------------------------
    for proc in actors.values():
        if proc.poll() is None:
            proc.terminate()
    actor_stats = []
    for aid, proc in actors.items():
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        path = os.path.join(out, f"actor{aid}_stats.json")
        try:
            with open(path) as f:
                actor_stats.append(json.load(f))
        except OSError:
            row(event="actor_stats_missing", actor=aid)

    # ---- the zero-loss ledger: survivor's landed rows vs actors' acks ----
    survivor = next(sid for sid in servers if sid != victim)
    acked_to_survivor = sum(
        int(s["acked_by_server"].get(str(survivor), 0)) for s in actor_stats)
    survivor_rows = -1
    try:
        hdr, _ = plane.peers[survivor].request({"op": "stats"}, timeout_s=10)
        survivor_rows = int(hdr.get("rows_appended", -1))
    except Exception as e:
        row(event="survivor_stats_failed", error=f"{type(e).__name__}: {e}")
    row(event="loss_ledger", survivor=survivor,
        survivor_rows_appended=survivor_rows,
        acked_to_survivor=acked_to_survivor)

    # ---- teardown ---------------------------------------------------------
    for proc in servers.values():
        if proc.poll() is None:
            proc.terminate()
    for proc in servers.values():
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
    plane.close()
    metrics.close()

    gates = {
        "discovered_all": discovered_peers == SERVERS
        and len(actor_stats) == 2
        and all(len(s["acked_by_server"]) == SERVERS for s in actor_stats),
        "learner_never_stalled": timeouts == 0
        and max_gap < args.stall_bound,
        "zero_lost_acked": acked_to_survivor > 0
        and survivor_rows >= acked_to_survivor,
        "readmit_restored": readmit_seen and revived_seen,
        "snapshot_acked_all": snapshot_acked == SERVERS,
    }
    result = {
        "path": "replay_net_smoke",
        "metric": "replay_net_smoke_batches_per_sec",
        "value": round(batches / max(wall_s, 1e-9), 1),
        "unit": "batches/s",
        "wall_s": round(wall_s, 2),
        "batches": batches,
        "rows_sampled": sc.rows_sampled,
        "updates_sent": sc.updates_sent,
        "rerouted": sc.rerouted,
        "max_gap_s": round(max_gap, 3),
        "get_timeouts": timeouts,
        "survivor_rows_appended": survivor_rows,
        "acked_to_survivor": acked_to_survivor,
        "snapshot_acked": snapshot_acked,
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
