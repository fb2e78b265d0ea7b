#!/usr/bin/env python
"""net_chaos_soak: all three wire planes surviving a SEEDED degraded
network, multi-process (`make netchaos-smoke`; docs/RESILIENCE.md
"degraded network").

The clean-death soaks (net_smoke, replay_net_smoke, chaos_soak) prove the
fleet survives SIGKILL; this one proves it survives the failure class
deployments actually die of — corruption, latency, one-way partitions —
injected by the ``netcore/chaos.py`` interposer at the socket seam every
plane already routes through.

Topology — every hop a REAL socket, every role a real process:

    parent:    the learner site — FrontRouter + EngineRegistry (serving),
               RemoteReplayPlane sampling (replay), ObsRelay streaming
               (telemetry), learner-role lease claimed at a fenced epoch
    children:  2 jax-free echo engines (TransportServer + engine lease),
               2 replay shard servers, 1 actor appender (acked-rows
               ledger), 1 obs collector, 1 warm standby (StandbyLearner)

The parent arms a ROTATING seeded schedule through one chaos spec with
@t windows (all relative to arming):  a corruption phase, a latency +
slow-read phase, then TWO one-way partitions at once (learner's egress
to replay shard 1 drops; engine 21's replies to the learner stall) — the
asymmetric-partition shape that splits brains.  Children arm their own
always-on low-rate corruption via ``RIA_NET_CHAOS`` env so server-side
read paths take hits too.

Self-asserted gates (exit 1 on any failure):

  1. every phase actually injected (the chaos ledger is causal: corrupt,
     delay, slow_read AND partition counts all nonzero — no vacuous pass);
  2. serving: ZERO lost accepted requests across the whole schedule
     (typed drops re-route; an asymmetric partition degrades ONLY the
     partitioned engine);
  3. replay: ZERO acked-then-lost transitions — every shard server's
     wire-reported ``rows_appended`` covers every row the actor counted
     as acked to it (at-least-once: corruption may duplicate, never lose);
  4. NO split brain: the warm standby held off for the entire schedule
     (the learner's lease kept beating through every network fault), and
     exactly ONE learner epoch exists after the heal;
  5. the fleet RE-CONVERGES within --mttr-bound of the heal: a serve
     completion, a sampled batch, and a collector ``fleet_health`` status
     ok row all land inside the bound;
  6. ``net_chaos`` rows naming the injected site are in the run dir, and
     the run dir lints as strict schema-versioned JSONL (the Makefile
     runs lint_jsonl after us).

Usage:
    JAX_PLATFORMS=cpu python scripts/net_chaos_soak.py \\
        --out /tmp/ria_netchaos_soak
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

RUN_ID = "net_chaos_soak"
FRAME = (12, 12)
SHARDS = 2           # replay shard servers (process ids 1..SHARDS)
LANES_PER_SHARD = 2
CAPACITY = 2048
ENGINES = (21, 22)   # engine lease host ids (chaos peer labels "engine21"…)
ACTOR_PID = 31
STANDBY_PID = 9
COLLECTOR_PID = 99


def row(**fields):
    print(json.dumps(fields), flush=True)


def soak_cfg(out_dir, process_id, seed=0, collector=False, **extra):
    from rainbow_iqn_apex_tpu.config import Config

    kwargs = dict(
        run_id=RUN_ID, seed=seed, results_dir=out_dir,
        process_id=process_id,
        replay_shards=SHARDS,
        heartbeat_interval_s=0.25,
        heartbeat_timeout_s=1.5,   # fast lease expiry for the soak
        replay_net_remote=True,
        obs_net=True,
        obs_net_spool=256,
        obs_net_snapshot_s=0.5,
        respawn_base_s=0.05,       # fast relay redial backoff
        respawn_max_s=0.5,
    )
    if collector:
        kwargs.update(
            obs_net_host="127.0.0.1",  # bind gate: this process IS the
            obs_net_stale_s=2.0,       # collector (ephemeral ports)
            obs_net_tick_s=0.3,
            obs_net_resolution_s=0.2,
        )
    kwargs.update(extra)  # per-role overrides win
    return Config(**kwargs)


def _lanes_total() -> int:
    return SHARDS * LANES_PER_SHARD


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


# ------------------------------------------------------- replay shard child
def shard_child(args) -> int:
    """One replay shard server under its env-armed chaos site (low-rate TX
    corruption: the ACK/sample-response direction takes hits too)."""
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatWriter,
        next_lease_epoch,
    )
    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
    from rainbow_iqn_apex_tpu.replay.net.server import ReplayShardServer
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    sid = args.child_id
    hb_dir = args.hb_dir
    epoch = next_lease_epoch(hb_dir, sid)
    memory = ShardedReplay.build(
        1, CAPACITY, LANES_PER_SHARD, frame_shape=FRAME, history=2,
        n_step=3, gamma=0.9, seed=args.seed + 100 * sid)
    run_dir = os.path.join(args.out, RUN_ID)
    os.makedirs(run_dir, exist_ok=True)
    logger = MetricsLogger(os.path.join(run_dir, f"shard{sid}.jsonl"),
                           run_id=RUN_ID, echo=False, host=sid)
    srv = ReplayShardServer(
        memory, shard_base=sid - 1, host="127.0.0.1", port=0, epoch=epoch,
        snapshot_prefix=os.path.join(args.out, f"replay_shard{sid}"),
        logger=logger).start()
    writer = HeartbeatWriter(hb_dir, sid, interval_s=0.25,
                             role="replay_shard", shard=sid - 1, epoch=epoch)
    srv.attach_lease(writer)
    writer.start()
    stop = _stop_event_for_child()
    while not stop.is_set():
        stop.wait(0.2)
    writer.stop()
    srv.stop()
    logger.close()
    return 0


# ------------------------------------------------------------- engine child
def engine_child(args) -> int:
    """One jax-free echo engine: try_submit/depth protocol server + pump
    thread + TransportServer, lease-advertised like a real engine host.
    The router's recovery paths (typed reroute, probe suspicion) care
    about the wire, not the model, so no jax is needed here."""
    import numpy as np

    from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatWriter
    from rainbow_iqn_apex_tpu.serving.batcher import ServeFuture
    from rainbow_iqn_apex_tpu.serving.net import TransportServer
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    eid = args.child_id

    class EchoServer:
        def __init__(self):
            self.q, self.lock = [], threading.Lock()

        def try_submit(self, obs):
            with self.lock:
                if len(self.q) >= 256:
                    return None
                fut = ServeFuture(np.asarray(obs))
                self.q.append(fut)
                return fut

        def depth(self):
            with self.lock:
                return len(self.q)

    run_dir = os.path.join(args.out, RUN_ID)
    os.makedirs(run_dir, exist_ok=True)
    logger = MetricsLogger(os.path.join(run_dir, f"engine{eid}.jsonl"),
                           run_id=RUN_ID, echo=False, host=eid)
    server = EchoServer()
    ts = TransportServer(server, port=0, logger=logger).start()
    writer = HeartbeatWriter(args.hb_dir, eid, interval_s=0.25,
                             role="engine")
    writer.update_payload(addr="127.0.0.1", port=ts.port)
    writer.start()
    stop = _stop_event_for_child()
    q = np.arange(6, dtype=np.float32)
    while not stop.is_set():
        with server.lock:
            pending, server.q = server.q, []
        for fut in pending:
            if not fut.cancelled():
                fut.set_result(3, q)
        stop.wait(0.003)
    writer.stop()
    ts.stop()
    logger.close()
    return 0


# -------------------------------------------------------------- actor child
def actor_child(args) -> int:
    """The appender whose acked ledger backs the zero-loss gate: only rows
    a shard server ACKED over the wire count; shed/spooled don't."""
    import numpy as np

    from rainbow_iqn_apex_tpu.replay.net.plane import RemoteReplayPlane
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    cfg = soak_cfg(args.out, process_id=ACTOR_PID, seed=args.seed,
                   obs_net=False)
    run_dir = os.path.join(args.out, RUN_ID)
    os.makedirs(run_dir, exist_ok=True)
    logger = MetricsLogger(os.path.join(run_dir, "actor.jsonl"),
                           run_id=RUN_ID, echo=False, host=ACTOR_PID)
    plane = RemoteReplayPlane(cfg, _lanes_total(), metrics=logger)
    rng = np.random.default_rng(args.seed + 7)
    stop = _stop_event_for_child()

    deadline = time.monotonic() + args.boot_timeout
    while (len(plane.peers) < SHARDS and not stop.is_set()
           and time.monotonic() < deadline):
        plane.poll(0)
        time.sleep(0.1)

    lanes = _lanes_total()
    tick = 0
    while not stop.is_set():
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
            plane.poll(tick)
        time.sleep(0.004)

    for ac in plane._appenders.values():
        ac.flush(timeout_s=10.0)
    stats = {
        "ticks": tick,
        "shed_lanes": plane.shed_lanes,
        "acked_by_server": {
            str(pid): ac.acked_rows for pid, ac in plane._appenders.items()
        },
    }
    path = os.path.join(args.out, "actor_stats.json")
    with open(path + ".tmp", "w") as f:
        json.dump(stats, f)
    os.replace(path + ".tmp", path)
    plane.close()
    logger.close()
    return 0


# ---------------------------------------------------------- collector child
def collector_child(args) -> int:
    from rainbow_iqn_apex_tpu.obs.net.collector import run_collector

    stop = _stop_event_for_child()
    cfg = soak_cfg(args.out, process_id=COLLECTOR_PID, seed=args.seed,
                   collector=True)
    run_collector(cfg, stop_event=stop)
    return 0


# ------------------------------------------------------------ standby child
def standby_child(args) -> int:
    """The split-brain witness: a warm standby polling the learner's lease
    through the whole schedule.  Network faults must never read as
    learner death (the lease is a file, and ``lease_skew_tolerance_s``
    absorbs reader/writer clock skew on top), so its ledger must show
    ZERO claims won."""
    from rainbow_iqn_apex_tpu.parallel.failover import (
        LEARNER_ROLE,
        StandbyLearner,
        latest_role_epoch,
    )
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    cfg = soak_cfg(args.out, process_id=STANDBY_PID, seed=args.seed,
                   obs_net=False, failover_standby=True,
                   lease_skew_tolerance_s=0.5)
    run_dir = os.path.join(args.out, RUN_ID)
    os.makedirs(run_dir, exist_ok=True)
    logger = MetricsLogger(os.path.join(run_dir, "standby.jsonl"),
                           run_id=RUN_ID, echo=False, host=STANDBY_PID)
    standby = StandbyLearner(cfg, takeover=lambda epoch, warm: "recovered",
                             metrics=logger)
    stop = _stop_event_for_child()
    polls = 0
    while not stop.is_set() and standby.result is None:
        standby.poll()
        polls += 1
        stop.wait(0.25)
    ledger = {
        "polls": polls,
        "claims_lost": standby.claims_lost,
        "took_over": standby.result is not None,
        "learner_epoch_seen": latest_role_epoch(standby.directory,
                                                LEARNER_ROLE),
    }
    path = os.path.join(args.out, "standby_stats.json")
    with open(path + ".tmp", "w") as f:
        json.dump(ledger, f)
    os.replace(path + ".tmp", path)
    logger.close()
    return 0


# ------------------------------------------------------------------ parent
def main() -> int:
    from rainbow_iqn_apex_tpu.netcore import chaos as netchaos

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--boot-grace", type=float, default=8.0,
                    help="quiet seconds after arming before the first phase")
    ap.add_argument("--phase", type=float, default=4.0,
                    help="seconds per fault phase (corrupt, then slow)")
    ap.add_argument("--partition", type=float, default=3.0,
                    help="seconds of the one-way partition phase")
    ap.add_argument("--post", type=float, default=16.0,
                    help="seconds of load after the heal (>= --mttr-bound)")
    ap.add_argument("--mttr-bound", type=float, default=15.0,
                    help="max seconds from heal to full re-convergence "
                         "(the sample plane's partition recovery is ~7s by "
                         "its probe/readmit cadence; the margin absorbs a "
                         "loaded CI machine)")
    ap.add_argument("--corrupt-p", type=float, default=0.04)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--boot-timeout", type=float, default=120.0)
    ap.add_argument("--out", default="/tmp/ria_netchaos_soak")
    # internal: child modes
    ap.add_argument("--role-child", default="", help=argparse.SUPPRESS)
    ap.add_argument("--child-id", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--hb-dir", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    child_mains = {"shard": shard_child, "engine": engine_child,
                   "actor": actor_child, "collector": collector_child,
                   "standby": standby_child}
    if args.role_child:
        return child_mains[args.role_child](args)

    import numpy as np

    from rainbow_iqn_apex_tpu.obs.net.relay import ObsRelay
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatWriter
    from rainbow_iqn_apex_tpu.parallel.failover import (
        LEARNER_ROLE,
        latest_role_epoch,
        learner_epoch_at_start,
    )
    from rainbow_iqn_apex_tpu.replay.net.plane import RemoteReplayPlane
    from rainbow_iqn_apex_tpu.serving.fleet import EngineRegistry, FrontRouter
    from rainbow_iqn_apex_tpu.serving.net import RemoteTransport
    from rainbow_iqn_apex_tpu.utils.faults import RetryPolicy
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    out = args.out
    os.makedirs(out, exist_ok=True)
    run_dir = os.path.join(out, RUN_ID)
    os.makedirs(run_dir, exist_ok=True)
    hb_dir = os.path.join(run_dir, "heartbeats")
    g, p, q = args.boot_grace, args.phase, args.partition
    heal_rel = g + 2 * p + q
    # the rotating schedule, one seeded spec (docstring: the @t windows are
    # seconds since arming; the parent arms right before plane boot)
    spec = ",".join([
        f"corrupt_frame@p={args.corrupt_p}@t={g}..{g + p}",
        f"delay_ms=30+-20@p=0.9@t={g + p}..{g + 2 * p}",
        f"slow_read_bps=256k@t={g + p}..{g + 2 * p}",
        f"partition=learner->replay1@t={g + 2 * p}..{heal_rel}",
        f"partition=engine{ENGINES[0]}->learner@t={g + 2 * p}..{heal_rel}",
    ])
    row(event="net_chaos_soak_start", spec=spec, seed=args.seed, out=out,
        heal_at_s=heal_rel)

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    def spawn(role, child_id, site, child_spec):
        child_env = dict(env)
        child_env[netchaos.ENV_VAR] = child_spec
        child_env[netchaos.SITE_ENV_VAR] = site
        child_env[netchaos.SEED_ENV_VAR] = str(args.seed)
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--role-child", role, "--child-id", str(child_id),
             "--hb-dir", hb_dir, "--out", out, "--seed", str(args.seed),
             "--boot-timeout", str(args.boot_timeout)],
            env=child_env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT)

    # children: always-on LOW-rate TX corruption at every serving/replay
    # site (server->client direction), so recv paths take seeded hits too;
    # the collector and standby run chaos-free (the standby owns no socket,
    # and the collector's fleet_health is the re-convergence witness)
    trickle = "corrupt_frame@p=0.005"
    children = {}
    for sid in range(1, SHARDS + 1):
        children[f"shard{sid}"] = spawn("shard", sid, f"replay{sid}", trickle)
    for eid in ENGINES:
        children[f"engine{eid}"] = spawn("engine", eid, f"engine{eid}",
                                         trickle)
    children["collector"] = spawn("collector", COLLECTOR_PID, "collector", "")
    children["actor"] = spawn("actor", ACTOR_PID, "actor",
                              "corrupt_frame@p=0.01")
    children["standby"] = spawn("standby", STANDBY_PID, "standby", "")

    def teardown(rc):
        for proc in children.values():
            if proc.poll() is None:
                proc.kill()
        return rc

    # ---- arm, then boot: sockets created from here on are interposed ----
    # failover_standby=True so learner_epoch_at_start writes a real role
    # claim marker — the split-brain gate checks the claimed epoch is
    # still the latest after the partition heals
    cfg = soak_cfg(out, process_id=0, seed=args.seed, failover_standby=True)
    metrics = MetricsLogger(os.path.join(run_dir, "learner.jsonl"),
                            run_id=RUN_ID, echo=False, host=0)
    armed = netchaos.install(
        netchaos.NetChaos(spec, seed=args.seed, site="learner"))
    armed.attach_logger(metrics)
    t_arm = time.monotonic()

    epoch = learner_epoch_at_start(cfg)
    lease = HeartbeatWriter(hb_dir, 0, interval_s=0.25, role=LEARNER_ROLE)
    lease.update_payload(learner_epoch=epoch)
    lease.start()

    retry = RetryPolicy(attempts=6, base_delay_s=0.1, max_delay_s=1.0,
                        seed=args.seed)
    registry = EngineRegistry(
        hb_dir, lease_timeout_s=cfg.heartbeat_timeout_s, logger=metrics,
        transport_factory=lambda lease_: RemoteTransport(
            lease_.addr, lease_.port, engine_id=lease_.host, retry=retry,
            probe_timeout_s=0.5, logger=metrics, connect=False),
        probe_timeout_s=0.5, probe_interval_s=0.5, net_stats_interval_s=2.0)
    router = FrontRouter(registry, max_inflight=256, logger=metrics,
                         metrics_interval_s=1.0, poll_interval_s=0.1)
    router.start()

    plane = RemoteReplayPlane(cfg, _lanes_total(), metrics=metrics)
    obs_registry = MetricRegistry()
    relay = ObsRelay.attach(cfg, metrics, registry=obs_registry,
                            role="learner")
    assert relay is not None  # cfg.obs_net is on

    # ---- boot: discovery through leases alone, warm replay rows ---------
    warm_rows = 4 * args.batch * SHARDS
    deadline = time.monotonic() + args.boot_timeout
    while time.monotonic() < deadline:
        plane.poll(0)
        if (len(plane.peers) == SHARDS
                and len(registry.routable()) == len(ENGINES)
                and plane.size() >= warm_rows and plane.sampleable()):
            break
        time.sleep(0.2)
    booted = (len(plane.peers) == SHARDS
              and len(registry.routable()) == len(ENGINES))
    row(event="fleet_booted", ok=booted, boot_s=round(armed.now(), 2),
        engines=len(registry.routable()), replay_peers=len(plane.peers),
        rows=plane.size())
    if not booted:
        row(path="net_chaos_soak", status="error",
            error=f"boot incomplete: engines={len(registry.routable())} "
                  f"replay={len(plane.peers)} rows={plane.size()}")
        return teardown(1)

    # ---- closed-loop serve clients across the schedule -------------------
    rng = np.random.default_rng(args.seed)
    obs_pool = rng.integers(0, 255, (16, 8, 8, 2), dtype=np.uint8)
    stop_ev = threading.Event()
    lock = threading.Lock()
    completions = []   # monotonic stamps of every completed request
    counts = {"completed": 0, "shed": 0, "errors": 0}

    def client(worker):
        i = 0
        while not stop_ev.is_set():
            try:
                fut = router.submit(obs_pool[(i + worker) % len(obs_pool)],
                                    tenant=f"t{worker}")
                fut.result(timeout=20)
                with lock:
                    counts["completed"] += 1
                    completions.append(time.monotonic())
            except Exception:  # shed AND typed wire errors: the gate is
                with lock:     # the router's lost==0, not per-try success
                    counts["errors"] += 1
                time.sleep(0.01)
            i += 1

    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(2)]
    for t in threads:
        t.start()

    # ---- the learner loop: sample straight through the schedule ----------
    sc = plane.start_sampling(args.batch, lambda: 0.5)
    t_heal = t_arm + heal_rel
    t_end = t_heal + max(args.post, args.mttr_bound)
    batch_stamps = []
    get_timeouts = 0
    step = 0
    # run to t_end, then keep sampling (hard-capped) until one POST-HEAL
    # batch lands: on a CPU-starved machine the fixed window can close
    # before the first post-heal batch, and "re-converged at t+Ns" is a
    # diagnosable gate failure where "never sampled again" is not
    t_hard = t_end + 2 * args.mttr_bound
    while time.monotonic() < t_end or (
            not any(s > t_heal for s in batch_stamps)
            and time.monotonic() < t_hard):
        step += 1
        try:
            s = sc.get(timeout=8.0)
        except TimeoutError:
            get_timeouts += 1
            continue
        batch_stamps.append(time.monotonic())
        sc.update_priorities(s.idx, np.abs(s.reward) + 0.01)
        if step % 32 == 0:
            plane.flush_writebacks()
        plane.poll(step)
    stop_ev.set()
    for t in threads:
        t.join(timeout=25)
    wall_s = time.monotonic() - t_arm

    # ---- MTTR: first proof of life on each plane after the heal ----------
    def mttr_of(stamps):
        after = [s - t_heal for s in stamps if s > t_heal]
        return round(min(after), 2) if after else None

    with lock:
        serve_mttr = mttr_of(completions)
    sample_mttr = mttr_of(batch_stamps)
    # the telemetry plane: the collector's own fleet_health row stream
    # (status ok, written after the heal) is the re-convergence witness
    t_heal_wall = time.time() - (time.monotonic() - t_heal)
    fleet_mttr = None
    collector_log = os.path.join(run_dir, "obs_collector.jsonl")
    fleet_deadline = time.monotonic() + args.mttr_bound
    while fleet_mttr is None and time.monotonic() < fleet_deadline:
        try:
            with open(collector_log) as f:
                for line in f:
                    try:
                        r = json.loads(line)
                    except ValueError:
                        continue
                    if (r.get("kind") == "fleet_health"
                            and r.get("status") == "ok"
                            and float(r.get("ts", 0)) > t_heal_wall):
                        fleet_mttr = round(r["ts"] - t_heal_wall, 2)
                        break
        except OSError:
            pass
        if fleet_mttr is None:
            time.sleep(0.3)
    row(event="reconvergence", serve_mttr_s=serve_mttr,
        sample_mttr_s=sample_mttr, fleet_mttr_s=fleet_mttr)

    # ---- drain the actor, then read the acked-rows ledgers ----------------
    children["actor"].terminate()
    try:
        children["actor"].wait(timeout=30)
    except subprocess.TimeoutExpired:
        children["actor"].kill()
    actor_stats = None
    try:
        with open(os.path.join(out, "actor_stats.json")) as f:
            actor_stats = json.load(f)
    except OSError:
        row(event="actor_stats_missing")
    shard_rows = {}
    for sid in range(1, SHARDS + 1):
        try:
            hdr, _ = plane.peers[sid].request({"op": "stats"}, timeout_s=10)
            shard_rows[sid] = int(hdr.get("rows_appended", -1))
        except Exception as e:
            shard_rows[sid] = -1
            row(event="shard_stats_failed", shard=sid,
                error=f"{type(e).__name__}: {e}")
    acked = {sid: int(actor_stats["acked_by_server"].get(str(sid), 0))
             if actor_stats else -1 for sid in range(1, SHARDS + 1)}
    row(event="loss_ledger", shard_rows_appended=shard_rows,
        acked_by_server=acked)

    # ---- the standby's split-brain ledger ---------------------------------
    children["standby"].terminate()
    try:
        children["standby"].wait(timeout=30)
    except subprocess.TimeoutExpired:
        children["standby"].kill()
    standby_stats = None
    try:
        with open(os.path.join(out, "standby_stats.json")) as f:
            standby_stats = json.load(f)
    except OSError:
        row(event="standby_stats_missing")
    final_epoch = latest_role_epoch(hb_dir, LEARNER_ROLE)

    # ---- teardown ---------------------------------------------------------
    stats = router.stop()
    for name, proc in children.items():
        if proc.poll() is None:
            proc.terminate()
    for proc in children.values():
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
    for handle in registry.handles():
        if handle.transport is not None and hasattr(handle.transport,
                                                    "close"):
            handle.transport.close()
    plane.close()
    relay.close(flush_timeout_s=2.0)
    lease.stop()
    metrics.close()

    injected = {f: armed.injected(f)
                for f in ("corrupt", "delay", "slow_read", "partition")}
    chaos_rows = 0
    with open(os.path.join(run_dir, "learner.jsonl")) as f:
        for line in f:
            try:
                r = json.loads(line)
            except ValueError:
                continue
            if r.get("kind") == "net_chaos" and r.get("site") == "learner":
                chaos_rows += 1

    gates = {
        "faults_injected_all_phases": all(n > 0 for n in injected.values()),
        "serving_zero_lost": stats["lost"] == 0 and counts["completed"] > 0,
        "replay_zero_lost_acked": (
            actor_stats is not None
            and sum(acked.values()) > 0
            and all(shard_rows[sid] >= acked[sid] >= 0
                    for sid in range(1, SHARDS + 1))),
        "no_split_brain": (
            standby_stats is not None
            and not standby_stats["took_over"]
            and final_epoch == epoch),
        "reconverged_within_mttr": all(
            m is not None and m <= args.mttr_bound
            for m in (serve_mttr, sample_mttr, fleet_mttr)),
        "chaos_rows_emitted": chaos_rows > 0,
    }
    result = {
        "path": "net_chaos_soak",
        "metric": "net_chaos_soak_completed_per_sec",
        "value": round(counts["completed"] / max(wall_s, 1e-9), 1),
        "unit": "completed serve requests/s across the fault schedule",
        "wall_s": round(wall_s, 2),
        "spec": spec,
        "injected": injected,
        "chaos_rows": chaos_rows,
        "completed": counts["completed"],
        "client_errors": counts["errors"],
        "router_stats": {k: stats[k] for k in ("accepted", "completed",
                                               "rerouted", "lost", "failed")},
        "batches": len(batch_stamps),
        "get_timeouts": get_timeouts,
        "serve_mttr_s": serve_mttr,
        "sample_mttr_s": sample_mttr,
        "fleet_mttr_s": fleet_mttr,
        "learner_epoch": final_epoch,
        "standby": standby_stats,
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
