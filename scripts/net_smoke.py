#!/usr/bin/env python
"""net_smoke: the cross-host serving plane proven end to end, multi-process
(`make net-smoke`; docs/SERVING.md "cross-host").

Topology — every hop a REAL socket, every engine a real process:

    parent: 2 FrontRouters (shared-nothing, own EngineRegistry each,
            federated over UDP RouterGossip) + 1 FleetRollout controller
    children: N engine hosts (default 3), each a separate process running
            PolicyServer + FleetEngine + TransportServer on 127.0.0.1:0,
            advertising addr:port through its lease payload

The routers discover the engines purely from the lease files (no port is
ever passed to the parent), dispatch a closed-loop client load across both
fronts, and mid-load one engine host is SIGKILLed cold — the true
process-death shape: no goodbye frame, connections drop, leases expire.
The rollout controller publishes int8-delta weight versions over the wire
before AND after the kill.

Self-asserted gates (exit 1 on any failure):

  1. both routers discovered all N engines through leases alone;
  2. ZERO lost accepted requests across both routers, through the kill
     (re-route fired: rerouted >= 1);
  3. the int8-delta rollout CONVERGED on every surviving engine, and each
     survivor's served-params digest equals the publisher's closed-loop
     reconstruction digest — bit-exact across the wire, asserted;
  4. the run dir lints as strict schema-versioned JSONL (route/net/gossip/
     rollout rows included — the Makefile runs lint_jsonl after us).

Usage:
    JAX_PLATFORMS=cpu python scripts/net_smoke.py --engines 3 --routers 2 \\
        --duration 6 --out /tmp/ria_net_smoke
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


def row(**fields):
    print(json.dumps(fields), flush=True)


def toy_cfg(run_id, seed, out_dir):
    from rainbow_iqn_apex_tpu.config import Config

    return Config(
        compute_dtype="float32",
        frame_height=44, frame_width=44, history_length=2,
        hidden_size=64, num_cosines=16,
        num_tau_samples=8, num_tau_prime_samples=8, num_quantile_samples=4,
        serve_batch_buckets="4,8,16",
        serve_deadline_ms=3.0,
        serve_queue_bound=64,
        serve_metrics_interval_s=1.0,
        fleet_lease_interval_s=0.25,
        fleet_lease_timeout_s=1.5,
        max_weight_lag=0,  # the smoke rolls versions mid-kill; survivors
        # must keep serving while a publish propagates, so no fence here
        serve_net_host="127.0.0.1",  # the cross-host on-switch: engine
        # children serve behind TransportServer.from_config
        run_id=run_id, seed=seed,
        results_dir=out_dir,
    )


# ------------------------------------------------------------- engine child
def engine_child(args) -> int:
    """One engine host: PolicyServer + FleetEngine lease + TransportServer,
    addr:port advertised in the lease BEFORE the first beat.  Runs until
    SIGTERM (clean stop) or SIGKILL (the victim's fate)."""
    import jax

    from rainbow_iqn_apex_tpu.serving import PolicyServer
    from rainbow_iqn_apex_tpu.serving.fleet import FleetEngine
    from rainbow_iqn_apex_tpu.serving.net import TransportServer
    from rainbow_iqn_apex_tpu.utils import quantize

    cfg = toy_cfg(f"net_smoke_e{args.engine_id}", args.seed, args.out)
    params = quantize.DeltaDecoder().apply(quantize.load_packet(args.params))
    server = PolicyServer(
        cfg, args.num_actions, params, devices=jax.devices()[:1],
        metrics_path=os.path.join(args.out, f"engine{args.engine_id}.jsonl"),
    )
    engine = FleetEngine(server, args.engine_id, args.hb_dir,
                         interval_s=cfg.fleet_lease_interval_s,
                         epoch=args.epoch)
    ts = TransportServer.from_config(cfg, engine,
                                     logger=server.metrics.logger)
    assert ts is not None  # toy_cfg sets serve_net_host
    ts.start()
    engine.start(warmup=True)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    ppid = os.getppid()
    while not stop.is_set():
        if os.getppid() != ppid:  # orphaned: the parent died, so should we
            break
        stop.wait(0.2)
    ts.stop()
    engine.stop()
    return 0


# ------------------------------------------------------------------ parent
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--engines", type=int, default=3)
    ap.add_argument("--routers", type=int, default=2)
    ap.add_argument("--duration", type=float, default=6.0,
                    help="seconds of client load")
    ap.add_argument("--clients-per-router", type=int, default=6)
    ap.add_argument("--kill-frac", type=float, default=0.4,
                    help="fraction of --duration at which a host is killed")
    ap.add_argument("--num-actions", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--boot-timeout", type=float, default=120.0)
    ap.add_argument("--out", default="/tmp/ria_net_smoke")
    # internal: engine-child mode
    ap.add_argument("--engine-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--engine-id", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--epoch", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--hb-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--params", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.engine_child:
        return engine_child(args)

    import numpy as np

    import jax

    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.parallel.elastic import HeartbeatMonitor
    from rainbow_iqn_apex_tpu.serving import ServerOverloaded
    from rainbow_iqn_apex_tpu.serving.fleet import (
        EngineRegistry,
        FleetRollout,
        FrontRouter,
    )
    from rainbow_iqn_apex_tpu.serving.net import (
        RemoteEngine,
        RemoteTransport,
        RouterGossip,
    )
    from rainbow_iqn_apex_tpu.utils import quantize
    from rainbow_iqn_apex_tpu.utils.faults import RetryPolicy
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    out = args.out
    os.makedirs(out, exist_ok=True)
    hb_dir = os.path.join(out, "heartbeats")
    cfg = toy_cfg("net_smoke", args.seed, out)
    state = init_train_state(cfg, args.num_actions, jax.random.PRNGKey(0))
    params_path = os.path.join(out, "boot_params.npz")
    quantize.save_packet(quantize.params_packet(state.params, 0), params_path)
    row(event="net_smoke_start", engines=args.engines, routers=args.routers,
        duration_s=args.duration, out=out)

    # ---- engine hosts: real processes, discovered only via leases --------
    children = {}
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    for eid in range(1, args.engines + 1):
        children[eid] = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--engine-child",
             "--engine-id", str(eid), "--hb-dir", hb_dir,
             "--params", params_path, "--out", out,
             "--seed", str(args.seed), "--num-actions",
             str(args.num_actions)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)

    # ---- routers: shared-nothing, lease-discovered, gossip-federated -----
    retry = RetryPolicy(attempts=6, base_delay_s=0.1, max_delay_s=1.0,
                        seed=args.seed)
    routers, registries, gossips, loggers = [], [], [], []
    for r in range(args.routers):
        logger = MetricsLogger(os.path.join(out, f"router{r}.jsonl"),
                               run_id="net_smoke", echo=False, host=r)
        obs_reg = MetricRegistry()
        registry = EngineRegistry(
            hb_dir, lease_timeout_s=cfg.fleet_lease_timeout_s,
            logger=logger, obs_registry=obs_reg,
            transport_factory=lambda lease, logger=logger: RemoteTransport(
                lease.addr, lease.port, engine_id=lease.host, retry=retry,
                probe_timeout_s=0.5, logger=logger, connect=False),
            probe_timeout_s=0.5, probe_interval_s=0.5,
            net_stats_interval_s=2.0)
        gossip = RouterGossip(
            r, snapshot_fn=lambda: {}, interval_s=0.25,
            logger=logger, obs_registry=obs_reg)
        router = FrontRouter(
            registry, max_inflight=256,
            logger=logger, obs_registry=obs_reg,
            metrics_interval_s=1.0, poll_interval_s=0.1,
            peer_inflight_fn=gossip.peer_inflight,
            peer_target_fn=gossip.peer_target_version)
        gossip.snapshot_fn = router.gossip_snapshot
        routers.append(router)
        registries.append(registry)
        gossips.append(gossip)
        loggers.append(logger)
    for r, gossip in enumerate(gossips):
        gossip.set_peers([("127.0.0.1", g.port)
                          for i, g in enumerate(gossips) if i != r])
        gossip.start()
    for router in routers:
        router.start()

    # ---- rollout controller: its OWN remote handles (shared-nothing too) -
    ctrl_logger = MetricsLogger(os.path.join(out, "controller.jsonl"),
                                run_id="net_smoke", echo=False, host=99)
    rollout = FleetRollout(logger=ctrl_logger, compression="int8_delta",
                           base_interval=4)
    monitor = HeartbeatMonitor(hb_dir, timeout_s=cfg.fleet_lease_timeout_s)
    remote_engines = {}

    def track_new_engines():
        for hid, lease in monitor.leases().items():
            if (lease.role == "engine" and lease.fresh and lease.addr
                    and lease.port and hid not in remote_engines):
                engine = RemoteEngine.from_lease(
                    lease, retry=retry, logger=ctrl_logger)
                remote_engines[hid] = engine
                rollout.track(engine)

    # ---- boot: every router must see every engine through leases alone ---
    deadline = time.monotonic() + args.boot_timeout
    while time.monotonic() < deadline:
        track_new_engines()
        if (len(remote_engines) == args.engines
                and all(len(reg.routable()) == args.engines
                        for reg in registries)):
            break
        time.sleep(0.25)
    discovered = {r: len(reg.routable()) for r, reg in enumerate(registries)}
    row(event="fleet_discovered", per_router=discovered,
        controller=len(remote_engines))
    if any(n != args.engines for n in discovered.values()):
        row(path="net_smoke", status="error",
            error=f"discovery incomplete: {discovered}")
        for proc in children.values():
            proc.kill()
        return 1

    rollout.publish(state.params, version=1)
    rollout.wait_converged(timeout_s=20.0)

    # ---- client load across both fronts ----------------------------------
    rng = np.random.default_rng(args.seed)
    obs_pool = rng.integers(0, 255, (32, 44, 44, 2), dtype=np.uint8)
    stop_ev = threading.Event()
    lock = threading.Lock()
    counts = {"completed": 0, "shed": 0, "errors": 0}

    def client(router, worker):
        i = 0
        while not stop_ev.is_set():
            try:
                fut = router.submit(obs_pool[(i + worker) % len(obs_pool)],
                                    tenant=f"t{worker % 3}")
                fut.result(timeout=30)
                with lock:
                    counts["completed"] += 1
            except ServerOverloaded:
                with lock:
                    counts["shed"] += 1
                time.sleep(0.005)
            except Exception:
                with lock:
                    counts["errors"] += 1
            i += 1

    threads = [threading.Thread(target=client, args=(router, w), daemon=True)
               for router in routers
               for w in range(args.clients_per_router)]
    t0 = time.monotonic()
    for t in threads:
        t.start()

    victim = min(children)
    killed = False
    rolled = 1
    kill_at = t0 + args.duration * args.kill_frac
    while time.monotonic() < t0 + args.duration:
        track_new_engines()
        rollout.sync()
        rollout.maybe_emit_converged()
        now = time.monotonic()
        if not killed and now >= kill_at:
            # catch the victim with UNANSWERED work queued: the closed-loop
            # clients alone keep engine queues near empty (a result already
            # in the TCP buffer at SIGKILL still reaches its client — no
            # re-route needed), so a burst of accepted requests is piled on
            # first and the kill lands while the victim's batcher is deep.
            # The burst futures re-route like any accepted request; the
            # drain loop below accounts for every one of them.
            burst = []
            for i in range(120):
                try:
                    burst.append(routers[i % len(routers)].submit(
                        obs_pool[i % len(obs_pool)], tenant="burst"))
                except ServerOverloaded:
                    pass
            spin_deadline = time.monotonic() + 2.0
            victim_handle = registries[0].get(victim)
            while (victim_handle is not None and victim_handle.depth() < 2
                   and time.monotonic() < spin_deadline):
                time.sleep(0.001)
            inflight_at_kill = sum(r.engine_inflight().get(victim, 0)
                                   for r in routers)
            children[victim].kill()  # SIGKILL: no goodbye frame, no drain
            killed = True
            row(event="engine_host_killed", engine=victim,
                inflight_at_kill=inflight_at_kill,
                at_s=round(now - t0, 2))
        if killed and rolled < 3 and now >= kill_at + 0.5 * rolled:
            rolled += 1
            perturbed = jax.tree.map(
                lambda x, k=rolled: x + 0.01 * k, state.params)
            rollout.publish(perturbed, version=rolled)
            row(event="rollout_fired", version=rolled)
        time.sleep(0.05)
    stop_ev.set()
    for t in threads:
        t.join(timeout=15)

    # ---- drain + converge + digest ---------------------------------------
    drain_deadline = time.monotonic() + 20
    while (any(r.inflight() > 0 for r in routers)
           and time.monotonic() < drain_deadline):
        rollout.sync()
        time.sleep(0.1)
    # the dead host cannot converge; drop it from the controller's view the
    # way an operator's autoscaler would after the lease expired
    rollout.untrack(victim)
    remote_engines.pop(victim, None)
    converged = rollout.wait_converged(timeout_s=20.0)
    target_digest = rollout.reconstructed_digest()
    digests = {eid: engine.served_digest(timeout_s=2.0)
               for eid, engine in remote_engines.items()}
    stats = [r.stop() for r in routers]
    for g in gossips:
        g.stop()
    gossip_received = sum(g.received for g in gossips)

    # ---- teardown ---------------------------------------------------------
    for eid, proc in children.items():
        if proc.poll() is None:
            proc.terminate()
    for proc in children.values():
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
    for engine in remote_engines.values():
        engine.transport.close()
    for registry in registries:
        for handle in registry.handles():
            if handle.transport is not None and hasattr(
                    handle.transport, "close"):
                handle.transport.close()
    for logger in loggers + [ctrl_logger]:
        logger.close()

    wall_s = time.monotonic() - t0
    total = {k: sum(s[k] for s in stats)
             for k in ("accepted", "completed", "shed", "rerouted", "lost",
                       "failed", "cancelled")}
    gates = {
        "discovered_all": all(n == args.engines
                              for n in discovered.values()),
        "lost_zero": total["lost"] == 0,
        "rerouted_after_kill": total["rerouted"] >= 1,
        "rollout_converged": converged,
        "survivors_bit_exact": (
            target_digest is not None and len(digests) == args.engines - 1
            and all(d == target_digest for d in digests.values())),
        "gossip_flowed": gossip_received >= 1,
        "no_client_errors": counts["errors"] == 0,
    }
    result = {
        "path": "net_smoke",
        "metric": "net_smoke_requests_per_sec",
        "value": round(total["completed"] / max(wall_s, 1e-9), 1),
        "unit": "req/s",
        "wall_s": round(wall_s, 2),
        "routers": args.routers,
        "engines": args.engines,
        **total,
        "client_completed": counts["completed"],
        "client_shed": counts["shed"],
        "client_errors": counts["errors"],
        "rollout_target": rollout.target_version,
        "survivor_digests_equal": gates["survivors_bit_exact"],
        "gossip_received": gossip_received,
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
