#!/usr/bin/env python
"""Load-generator bench for the policy server (serving/): N synthetic client
threads drive `PolicyServer.act` as fast as the server completes them, with a
weight hot-swap fired mid-run, and the result is printed as JSON rows
(one object per line, flushed immediately, LAST line is the headline
requests/sec).

What is measured: end-to-end serving throughput and latency through the real
stack — bounded queue, deadline coalescing, bucket padding, lane-sharded
jitted inference, atomic param swap — not a model microbenchmark.  Batch
occupancy tells whether micro-batching actually coalesced (the acceptance
gate is mean occupancy > 4 at 64 clients); shed_total must be 0 when clients
<= queue bound (blocking clients can never overrun it).

CPU smoke shape (default): 44x44x2 frames, hidden 64, IQN taus 8/8/4 — the
same small-but-real network the parallel tests use, so the numbers track the
serving machinery, not conv throughput.

``--fleet-soak`` switches to the heavy-traffic fleet scenario
(serving/fleet/, docs/SERVING.md "fleet"): an in-process router + N-engine
fleet under bursty OPEN-LOOP arrivals from multiple QoS tenants, a cohort of
deliberately slow clients that abandon (cancel) their requests, one engine
killed cold mid-load (lease expiry -> re-route; the supervisor respawns it
with backoff), and two fleet-wide weight rollouts — one of which is a
deliberate BACKWARD publish that must be refused.  Gates (enforced, exit 1):
zero lost accepted requests, every accepted request accounted for, p99 and
shed-rate bounds, rollout convergence with no version rollback.  The result
is one ``fleet_soak`` row in the PR-6 budgeted-row convention (no ``status``
key when healthy; ``"status": "error"/"gate_failed"`` otherwise), plus a
lint-clean run dir of route/scale/rollout/serve JSONL.

``--fleet-soak --net`` runs the SAME scenario with a real loopback socket on
every hop (serving/net/): engines behind `TransportServer`s, the router
dispatching through `RemoteTransport`s, rollouts shipped as int8-delta
packets over the wire with bit-exact adoption gated per engine.  Emits one
``net_soak`` row (aggregate rps, p99, rollout bytes over the wire vs fp32).

``--quant`` runs the fp32-vs-int8 serving comparison (`make quant-smoke`):
the same fixed load through a fp32 engine and a quantized one
(``serve_quantize="int8"``, agreement-gated), one ``quant_serve`` row with
both modes' req/s + p99 and the gate outcome — the gate MUST activate the
quantized path and both modes must complete every request (exit 1
otherwise).

Usage:
    JAX_PLATFORMS=cpu python scripts/bench_serve.py --clients 64 --requests 2000
    JAX_PLATFORMS=cpu python scripts/bench_serve.py --fleet-soak --engines 2
    JAX_PLATFORMS=cpu python scripts/bench_serve.py --quant --clients 16
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def row(**fields):
    """One JSON line, stamped with the device the run is on (every mode has
    brought jax up by its first row)."""
    import jax

    dev = jax.devices()
    print(json.dumps({**fields, "platform": dev[0].platform,
                      "device_kind": dev[0].device_kind,
                      "device_count": len(dev)}), flush=True)


class _InProcFleet:
    """The soak's in-process fleet: N PolicyServers wrapped as FleetEngines
    (lease self-registration in a shared heartbeat dir), one EngineRegistry +
    FrontRouter over them, a RoleSupervisor-backed Autoscaler, and a
    FleetRollout — the full serving/fleet composition on one host.

    ``net=True`` (the ``--net`` soak variant) keeps the same topology but
    puts a REAL loopback socket on every hop: each engine serves behind a
    `TransportServer`, the router dispatches through `RemoteTransport`s,
    and the rollout ships int8-delta packets to `RemoteEngine` proxies —
    the full serving/net wire path under the same bursty load and kill."""

    def __init__(self, cfg, num_actions, params, out_dir, net=False):
        import jax

        from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
        from rainbow_iqn_apex_tpu.parallel.elastic import RoleSupervisor
        from rainbow_iqn_apex_tpu.serving import PolicyServer
        from rainbow_iqn_apex_tpu.serving.fleet import (
            Autoscaler,
            EngineRegistry,
            FleetEngine,
            FleetRollout,
            FrontRouter,
            ScalePolicy,
        )
        from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

        self.cfg = cfg
        self.num_actions = num_actions
        self.params = params
        self.out_dir = out_dir
        self.net = bool(net)
        self._jax = jax
        self._PolicyServer = PolicyServer
        self._FleetEngine = FleetEngine
        self.logger = MetricsLogger(
            os.path.join(out_dir, "metrics.jsonl"), run_id=cfg.run_id,
            echo=False)
        self.obs = MetricRegistry()
        self.hb_dir = os.path.join(out_dir, "heartbeats")
        self.registry = EngineRegistry(
            self.hb_dir, lease_timeout_s=cfg.fleet_lease_timeout_s,
            logger=self.logger, obs_registry=self.obs,
            probe_timeout_s=cfg.serve_net_probe_timeout_s,
            probe_interval_s=cfg.serve_net_probe_interval_s,
            net_stats_interval_s=2.0)
        # --net ships every rollout as int8-delta packets over the wire —
        # the QuaRL byte win is only real once weights actually cross one
        self.rollout = FleetRollout(
            logger=self.logger, obs_registry=self.obs,
            compression="int8_delta" if self.net else "off",
            base_interval=cfg.publish_base_interval)
        self.router = FrontRouter.from_config(
            cfg, self.registry, target_version_fn=self.rollout.version,
            logger=self.logger, obs_registry=self.obs)
        self.router.metrics_interval_s = 1.0
        self.supervisor = RoleSupervisor.from_config(
            cfg, metrics=self.logger, registry=self.obs)
        self.autoscaler = Autoscaler(
            ScalePolicy.from_config(cfg),
            spawn_engine=self.spawn_engine,
            stop_engine=self.stop_engine,
            load_fn=self.load,
            supervisor=self.supervisor,
            logger=self.logger, obs_registry=self.obs)
        self.engines = {}
        self.tservers = {}
        self.transports = {}

    def spawn_engine(self, engine_id, epoch):
        """Boot one engine (fresh PolicyServer + lease at ``epoch``), attach
        it to the registry and catch it up to the rollout target.  Also the
        supervisor's respawn path after a kill."""
        server = self._PolicyServer(
            self.cfg, self.num_actions, self.params,
            devices=self._jax.devices()[:1],
            metrics_path=os.path.join(self.out_dir, f"engine{engine_id}.jsonl"),
        )
        engine = self._FleetEngine(
            server, engine_id, self.hb_dir,
            interval_s=self.cfg.fleet_lease_interval_s, epoch=epoch)
        if self.net:
            from rainbow_iqn_apex_tpu.serving.net import (
                RemoteEngine,
                RemoteTransport,
                TransportServer,
            )

            # the config seam is the on-switch: serve_net_host set by --net
            ts = TransportServer.from_config(self.cfg, engine,
                                             logger=self.logger)
            assert ts is not None, "--net requires serve_net_host"
            ts.start()
            engine.start(warmup=True)
            old = self.transports.get(engine_id)
            if old is not None:  # respawn after a kill: retire the corpse's
                old.close()      # client before attaching the new one
            transport = RemoteTransport(
                "127.0.0.1", ts.port, engine_id=engine_id,
                probe_timeout_s=self.cfg.serve_net_probe_timeout_s,
                logger=self.logger, obs_registry=self.obs)
            self.tservers[engine_id] = ts
            self.transports[engine_id] = transport
            self.engines[engine_id] = engine
            self.registry.attach(engine_id, transport)
            self.rollout.track(RemoteEngine(engine_id, transport))
        else:
            engine.start(warmup=True)
            self.engines[engine_id] = engine
            self.registry.attach(engine_id, engine.transport)
            self.rollout.track(engine)
        self.rollout.sync()
        return engine.proc()

    def stop_engine(self, engine_id):
        engine = self.engines.pop(engine_id, None)
        if engine is not None:
            self.rollout.untrack(engine_id)
            self.registry.detach(engine_id)
            engine.stop()
        ts = self.tservers.pop(engine_id, None)
        if ts is not None:
            ts.stop()
        transport = self.transports.pop(engine_id, None)
        if transport is not None:
            transport.close()

    def kill_engine(self, engine_id):
        """The mid-soak SIGKILL analog: heartbeats stop cold, queued
        requests fail NOW (the router re-routes them), the lease expires on
        the monitor's clock and the supervisor respawns with backoff.  In
        --net mode the transport listener drops FIRST — clients see the
        connection die exactly like a host death, before any engine-side
        cleanup could leak a polite goodbye."""
        ts = self.tservers.pop(engine_id, None)
        if ts is not None:
            ts.stop()
        engine = self.engines.get(engine_id)
        if engine is not None:
            engine.kill()

    def load(self):
        return {
            "engines": len(self.registry.routable()),
            "depth_frac": self.router.mean_depth_fraction(
                self.cfg.serve_queue_bound),
            "p99_ms": self.router.p99_ms(),
        }

    def start(self, n_engines):
        for i in range(n_engines):
            proc = self.spawn_engine(i, 0)
            self.autoscaler.adopt_engine(i, proc=proc)
        self.router.start()

    def stop(self):
        self.router.stop()
        self.supervisor.stop_all()
        for engine_id in list(self.engines):
            self.stop_engine(engine_id)
        self.logger.close()


def quant_bench(args) -> int:
    """``--quant``: fp32 vs int8 serving through the REAL stack at fixed
    load (same clients/requests/buckets), one ``quant_serve`` row with both
    modes' req/s and p99 plus the gate outcome.  Gates (exit 1): the int8
    engine's agreement gate must ACTIVATE the quantized path (this is the
    one real-engine int8 serve `make quant-smoke` requires), and both modes
    must complete every request.

    Honest-numbers note: on the CPU backend weight-only int8 adds an
    in-graph dequantize to every dispatch, so ``speedup_vs_fp32`` near (or
    under) 1.0 here is expected — the capacity win is an accelerator
    story (HBM bandwidth + smaller broadcasts); what this smoke proves is
    the gate, the serving correctness, and the row/metrics surface."""
    import numpy as np

    import jax

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.serving import PolicyServer

    out_dir = (args.out if args.out != "results/serve_bench"
               else "results/quant_bench")
    os.makedirs(out_dir, exist_ok=True)

    def run_mode(quant_mode, params):
        cfg = Config(
            compute_dtype="float32",
            frame_height=44, frame_width=44, history_length=2,
            hidden_size=64, num_cosines=16,
            num_tau_samples=8, num_tau_prime_samples=8,
            num_quantile_samples=4,
            serve_batch_buckets=args.buckets,
            serve_deadline_ms=args.deadline_ms,
            serve_queue_bound=args.queue_bound,
            serve_mode=args.mode,
            serve_metrics_interval_s=1.0,
            serve_quantize=quant_mode,
            quant_agreement_min=args.agreement_min,
            run_id=f"quant_bench_{quant_mode}",
            seed=args.seed,
        )
        server = PolicyServer(
            cfg, args.num_actions, params,
            metrics_path=os.path.join(out_dir, f"serve_{quant_mode}.jsonl"),
        )
        server.start()
        rng = np.random.default_rng(args.seed)
        obs_pool = rng.integers(0, 255, (64, 44, 44, 2), dtype=np.uint8)
        issued = threading.Semaphore(args.requests)
        done = [0]
        lock = threading.Lock()
        errors = []

        def client(idx):
            while issued.acquire(blocking=False):
                try:
                    server.act(obs_pool[idx % len(obs_pool)], timeout=120)
                except Exception as e:  # noqa: BLE001
                    errors.append(f"{type(e).__name__}: {e}")
                    return
                with lock:
                    done[0] += 1

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(args.clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        quant_state = server.engine.quant_state()
        stats = server.stop()
        return {
            "rps": done[0] / max(wall, 1e-9),
            "p99_ms": stats.get("latency_p99_ms"),
            "completed": done[0],
            "errors": len(errors),
            "quant_active": quant_state["quant_active"],
            "quant_agreement": quant_state["quant_agreement"],
            "quant_fallbacks": quant_state["quant_fallbacks"],
        }

    state = init_train_state(
        Config(compute_dtype="float32", frame_height=44, frame_width=44,
               history_length=2, hidden_size=64, num_cosines=16,
               num_tau_samples=8, num_tau_prime_samples=8,
               num_quantile_samples=4),
        args.num_actions, jax.random.PRNGKey(0))
    row(event="quant_bench_start", clients=args.clients,
        requests=args.requests, out=out_dir)
    fp32 = run_mode("off", state.params)
    row(event="quant_bench_fp32_done", **fp32)
    int8 = run_mode("int8", state.params)
    row(event="quant_bench_int8_done", **int8)

    gates = {
        "int8_gate_activated": bool(int8["quant_active"]),
        "fp32_completed": fp32["completed"] == args.requests,
        "int8_completed": int8["completed"] == args.requests,
        "no_errors": fp32["errors"] == 0 and int8["errors"] == 0,
    }
    result = {
        "path": "quant_serve",
        "metric": "quant_serve_requests_per_sec",
        "value": round(int8["rps"], 1),
        "unit": "req/s (int8 engine; fp32 row alongside)",
        "rps_fp32": round(fp32["rps"], 1),
        "rps_int8": round(int8["rps"], 1),
        "speedup_vs_fp32": round(int8["rps"] / max(fp32["rps"], 1e-9), 3),
        "p99_fp32_ms": fp32["p99_ms"],
        "p99_int8_ms": int8["p99_ms"],
        "agreement": int8["quant_agreement"],
        "quant_active": int8["quant_active"],
        "quant_fallbacks": int8["quant_fallbacks"],
        "requests_per_mode": args.requests,
        "gates": gates,
    }
    if not all(gates.values()):
        result["status"] = "gate_failed"
        row(**result)
        return 1
    row(**result)
    return 0


def fleet_soak(args) -> int:
    import numpy as np

    import jax

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.serving import ServerOverloaded

    out_dir = (args.out if args.out != "results/serve_bench"
               else ("results/net_soak" if args.net
                     else "results/fleet_soak"))
    os.makedirs(out_dir, exist_ok=True)
    cfg = Config(
        compute_dtype="float32",
        frame_height=44, frame_width=44, history_length=2,
        hidden_size=64, num_cosines=16,
        num_tau_samples=8, num_tau_prime_samples=8, num_quantile_samples=4,
        serve_batch_buckets=args.buckets,
        serve_deadline_ms=args.deadline_ms,
        serve_queue_bound=64,  # small per-engine bound: the soak WANTS
        # backpressure visible at the router, not hidden in deep queues
        serve_mode=args.mode,
        serve_metrics_interval_s=1.0,
        fleet_min_engines=args.engines,
        fleet_max_engines=args.max_engines,
        fleet_max_inflight=256,
        fleet_tenant_rate=args.rate,  # one tenant alone cannot flood the
        fleet_tenant_burst=64,        # fleet past the aggregate target rate
        fleet_lease_interval_s=0.25,
        fleet_lease_timeout_s=1.5,
        fleet_scale_patience=3,
        fleet_scale_cooldown_s=2.0,
        max_weight_lag=1,  # a respawned engine serves only after it is
        # caught up to within one publish of the rollout target
        respawn_base_s=0.2, respawn_max_s=1.0,
        publish_base_interval=2,  # --net: v1 base + v2 delta, so the wire
        # rollout exercises BOTH packet kinds and the late-joiner chain
        serve_net_host="127.0.0.1" if args.net else "",  # the cross-host
        # on-switch: engines serve behind TransportServer.from_config
        run_id="net_soak" if args.net else "fleet_soak",
        seed=args.seed,
    )
    state = init_train_state(cfg, args.num_actions, jax.random.PRNGKey(0))
    fleet = _InProcFleet(cfg, args.num_actions, state.params, out_dir,
                         net=args.net)
    row(event="net_soak_start" if args.net else "fleet_soak_start",
        engines=args.engines,
        max_engines=args.max_engines, duration_s=args.duration,
        rate=args.rate, out=out_dir)
    t0 = time.monotonic()
    fleet.start(args.engines)
    fleet.rollout.publish(state.params, version=1)
    row(event="fleet_up", engines=len(fleet.engines),
        boot_s=round(time.monotonic() - t0, 2))

    rng = np.random.default_rng(args.seed)
    obs_pool = rng.integers(0, 255, (64, 44, 44, 2), dtype=np.uint8)
    stop_ev = threading.Event()
    lock = threading.Lock()
    counts = {"submitted": 0, "shed": 0, "slow_submitted": 0,
              "slow_cancelled": 0, "slow_served": 0}
    latencies = []

    def collect(fut):
        if fut.cancelled():
            return
        try:
            fut.result(timeout=0)
        except Exception:
            return
        with lock:
            latencies.append((time.monotonic() - fut.t_enqueue) * 1e3)

    # three tenants across the QoS tiers; "burst" rides the lowest class so
    # its flood sheds FIRST under pressure (the QoS story, observable in the
    # route rows' shed_by_reason/tenants split)
    tenants = [("gold_t", "gold", 0.2), ("std_t", "std", 0.5),
               ("burst_t", "batch", 0.3)]

    def arrivals(worker_seed):
        """Open-loop generator: submissions happen on the wall-clock
        schedule whether or not the fleet keeps up — the IMPACT-style
        decoupling the admission layer exists for."""
        wrng = np.random.default_rng(worker_seed)
        t_end = t0_load + args.duration
        i = 0
        while not stop_ev.is_set() and time.monotonic() < t_end:
            phase = ((time.monotonic() - t0_load) % args.burst_period
                     < args.burst_period * 0.5)
            rate = args.rate * (args.burst_factor if phase else 0.3)
            time.sleep(min(float(wrng.exponential(1.0 / max(rate, 1e-6))),
                           0.05))
            r = wrng.random()
            acc = 0.0
            for name, qos, share in tenants:
                acc += share
                if r <= acc:
                    break
            with lock:
                counts["submitted"] += 1
            try:
                fut = fleet.router.submit(
                    obs_pool[i % len(obs_pool)], tenant=name, qos=qos)
                fut.add_done_callback(collect)
            except ServerOverloaded:
                with lock:
                    counts["shed"] += 1
            i += 1

    def slow_client(worker_seed):
        """Deliberately slow cohort: submit, give up almost immediately,
        CANCEL — abandoned futures must not burn batch capacity
        (serve_cancelled_total counts the skips)."""
        wrng = np.random.default_rng(worker_seed)
        t_end = t0_load + args.duration
        i = 0
        while not stop_ev.is_set() and time.monotonic() < t_end:
            with lock:
                counts["slow_submitted"] += 1
            try:
                fut = fleet.router.submit(
                    obs_pool[i % len(obs_pool)], tenant="slow_t", qos="batch")
            except ServerOverloaded:
                time.sleep(0.01)
                continue
            try:
                fut.result(timeout=args.slow_timeout)
                with lock:
                    counts["slow_served"] += 1
            except TimeoutError:
                fut.cancel()
                with lock:
                    counts["slow_cancelled"] += 1
            except Exception:
                pass  # engine-kill window: the error is the router's story
            time.sleep(float(wrng.exponential(0.02)))
            i += 1

    t0_load = time.monotonic()
    threads = [threading.Thread(target=arrivals, args=(args.seed + 1,),
                                daemon=True)]
    threads += [threading.Thread(target=slow_client, args=(args.seed + 10 + k,),
                                 daemon=True)
                for k in range(args.slow_clients)]
    for t in threads:
        t.start()

    killed = rolled_v2 = refused_checked = False
    kill_at = t0_load + args.duration * args.kill_frac
    while time.monotonic() < t0_load + args.duration:
        fleet.autoscaler.evaluate()
        fleet.rollout.sync()
        fleet.rollout.maybe_emit_converged()
        now = time.monotonic()
        if not killed and now >= kill_at:
            victim = min(fleet.engines)
            # catch the victim with requests QUEUED, so the kill provably
            # exercises the re-route path (gated rerouted >= 1 below) —
            # under open-loop load this spin resolves in milliseconds
            spin_deadline = time.monotonic() + 2.0
            transport = fleet.engines[victim].transport
            while (transport.depth() < 2
                   and time.monotonic() < spin_deadline):
                time.sleep(0.001)
            depth_at_kill = transport.depth()
            fleet.kill_engine(victim)
            killed = True
            row(event="engine_killed", engine=victim,
                depth_at_kill=depth_at_kill,
                at_s=round(now - t0_load, 2))
        if killed and not rolled_v2 and now >= kill_at + 0.5:
            perturbed = jax.tree.map(lambda x: x + 0.01, state.params)
            fleet.rollout.publish(perturbed, version=2)
            rolled_v2 = True
            row(event="rollout_fired", version=2)
        if rolled_v2 and not refused_checked:
            refused = fleet.rollout.publish(state.params, version=1)
            refused_checked = True
            row(event="backward_publish_refused",
                ok=refused.get("event") == "refused_backward")
        time.sleep(0.2)
    stop_ev.set()
    for t in threads:
        t.join(timeout=10)

    # drain: every accepted request must settle (complete, cancel or — the
    # gated failure — be lost); respawn/rollout stragglers get a last sync
    drain_deadline = time.monotonic() + 30
    while fleet.router.inflight() > 0 and time.monotonic() < drain_deadline:
        fleet.autoscaler.evaluate()
        fleet.rollout.sync()
        time.sleep(0.1)
    converged = fleet.rollout.wait_converged(timeout_s=15.0)
    versions = fleet.rollout.engine_versions()
    wall_s = time.monotonic() - t0_load
    stats = fleet.router.stats()
    net_capture = None
    if args.net:  # captured BEFORE stop() tears the engine/transport maps down
        net_capture = {
            "target_digest": fleet.rollout.reconstructed_digest(),
            "digests": {str(eid): e.served_digest
                        for eid, e in fleet.engines.items()
                        if e.transport.alive()},
            "rollout_bytes_wire": fleet.rollout.bytes_total,
            "publishes": fleet.rollout.publishes,
            "transport_bytes_sent": sum(
                t.bytes_sent for t in fleet.transports.values()),
            "transport_reconnects": sum(
                t.reconnects for t in fleet.transports.values()),
        }
    fleet.stop()

    lat = sorted(latencies)
    p99 = lat[min(int(len(lat) * 0.99), len(lat) - 1)] if lat else None
    p50 = lat[len(lat) // 2] if lat else None
    accepted = stats["accepted"]
    settled = (stats["completed"] + stats["cancelled"] + stats["failed"]
               + stats["lost"])
    shed_rate = stats["shed"] / max(counts["submitted"]
                                    + counts["slow_submitted"], 1)
    gates = {
        "lost_zero": stats["lost"] == 0,
        "accepted_accounted": settled == accepted,
        "p99_ms": p99 is not None and p99 <= args.p99_gate_ms,
        "shed_rate": shed_rate <= args.shed_gate,
        # the kill waited for queued requests on the victim, so the re-route
        # path MUST have fired — a vacuous pass here would mean the soak
        # never exercised what it claims to gate
        "rerouted_after_kill": stats["rerouted"] >= 1,
        "rollout_converged": converged,
        # the deliberate backward publish was refused AND the fleet target
        # ended where the forward publishes left it — no rollback happened
        "no_rollback": (fleet.rollout.refused == 1
                        and fleet.rollout.target_version == 2),
        "cancel_worked": counts["slow_cancelled"] == 0
        or stats["cancelled"] > 0,
    }
    soak_path = "net_soak" if args.net else "fleet_soak"
    net_fields = {}
    if net_capture is not None:
        # wire weight-rollout economics: bytes the int8-delta packets
        # actually shipped vs what fp32-full would have — the QuaRL/PR-8
        # ratio measured ACROSS a socket
        from rainbow_iqn_apex_tpu.utils.quantize import tree_bytes

        fp32_total = tree_bytes(state.params) * net_capture["publishes"]
        gates["wire_rollout_bit_exact"] = (
            bool(net_capture["digests"])
            and all(d == net_capture["target_digest"]
                    for d in net_capture["digests"].values()))
        net_fields = {
            "rollout_bytes_wire": net_capture["rollout_bytes_wire"],
            "rollout_bytes_fp32": fp32_total,
            "rollout_bytes_ratio_vs_fp32": round(
                fp32_total / max(net_capture["rollout_bytes_wire"], 1), 3),
            "transport_bytes_sent": net_capture["transport_bytes_sent"],
            "transport_reconnects": net_capture["transport_reconnects"],
        }
    result = {
        "path": soak_path,
        "metric": f"{soak_path}_requests_per_sec",
        "value": round(stats["completed"] / max(wall_s, 1e-9), 1),
        "unit": "req/s",
        **net_fields,
        "wall_s": round(wall_s, 2),
        "submitted": counts["submitted"] + counts["slow_submitted"],
        "accepted": accepted,
        "completed": stats["completed"],
        "shed": stats["shed"],
        "shed_rate": round(shed_rate, 4),
        "shed_by_reason": stats["shed_by_reason"],
        "rerouted": stats["rerouted"],
        "lost": stats["lost"],
        "cancelled": stats["cancelled"],
        "slow_cancelled": counts["slow_cancelled"],
        "latency_p50_ms": None if p50 is None else round(p50, 2),
        "latency_p99_ms": None if p99 is None else round(p99, 2),
        "engine_versions": {str(k): v for k, v in versions.items()},
        "rollout_converged": converged,
        "tenants": stats["tenants"],
        "gates": gates,
    }
    if not all(gates.values()):
        result["status"] = "gate_failed"
        row(**result)
        return 1
    row(**result)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--deadline-ms", type=float, default=5.0)
    ap.add_argument("--buckets", default="8,16,32,64")
    ap.add_argument("--queue-bound", type=int, default=256)
    ap.add_argument("--mode", default="greedy", choices=("greedy", "noisy"))
    ap.add_argument("--no-swap", action="store_true",
                    help="skip the mid-bench weight hot-swap")
    ap.add_argument("--num-actions", type=int, default=6)
    ap.add_argument("--out", default="results/serve_bench",
                    help="directory for the JSONL metrics log")
    # ---- quantized serving (utils/quantize.py; make quant-smoke) ----
    ap.add_argument("--quant", action="store_true",
                    help="run the fp32-vs-int8 serving comparison instead")
    ap.add_argument("--agreement-min", type=float, default=0.99,
                    help="greedy-action agreement gate threshold (--quant)")
    # ---- fleet soak (serving/fleet/) ----
    ap.add_argument("--fleet-soak", action="store_true",
                    help="run the router+fleet heavy-traffic soak instead")
    ap.add_argument("--net", action="store_true",
                    help="with --fleet-soak: put a real loopback socket on "
                         "every hop (TransportServer/RemoteTransport) and "
                         "ship rollouts as int8-delta packets over the "
                         "wire; emits one net_soak row")
    ap.add_argument("--engines", type=int, default=2,
                    help="initial engine count (fleet soak)")
    ap.add_argument("--max-engines", type=int, default=3,
                    help="autoscaler ceiling (fleet soak)")
    ap.add_argument("--duration", type=float, default=8.0,
                    help="seconds of open-loop arrivals (fleet soak)")
    ap.add_argument("--rate", type=float, default=250.0,
                    help="mean arrivals/s across tenants (fleet soak)")
    ap.add_argument("--burst-factor", type=float, default=3.0,
                    help="hi-phase arrival multiplier (lo phase = 0.3x)")
    ap.add_argument("--burst-period", type=float, default=2.0)
    ap.add_argument("--slow-clients", type=int, default=3,
                    help="cohort of clients that abandon (cancel) requests")
    ap.add_argument("--slow-timeout", type=float, default=0.03,
                    help="seconds a slow client waits before giving up")
    ap.add_argument("--kill-frac", type=float, default=0.5,
                    help="fraction of --duration at which an engine is killed")
    ap.add_argument("--p99-gate-ms", type=float, default=2000.0)
    ap.add_argument("--shed-gate", type=float, default=0.6,
                    help="max tolerated shed fraction of submissions")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.net and not args.fleet_soak:
        ap.error("--net is a --fleet-soak variant")
    if args.fleet_soak:
        return fleet_soak(args)
    if args.quant:
        return quant_bench(args)

    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.serving import PolicyServer

    cfg = Config(
        compute_dtype="float32",
        frame_height=44,
        frame_width=44,
        history_length=2,
        hidden_size=64,
        num_cosines=16,
        num_tau_samples=8,
        num_tau_prime_samples=8,
        num_quantile_samples=4,
        serve_batch_buckets=args.buckets,
        serve_deadline_ms=args.deadline_ms,
        serve_queue_bound=args.queue_bound,
        serve_mode=args.mode,
        serve_metrics_interval_s=1.0,
        run_id="serve_bench",
    )
    state = init_train_state(cfg, args.num_actions, jax.random.PRNGKey(0))
    os.makedirs(args.out, exist_ok=True)
    metrics_path = os.path.join(args.out, "metrics.jsonl")
    server = PolicyServer(
        cfg, args.num_actions, state.params, metrics_path=metrics_path
    )
    row(event="bench_serve_start", clients=args.clients, requests=args.requests,
        buckets=server.engine.buckets, deadline_ms=args.deadline_ms,
        queue_bound=args.queue_bound, devices=server.engine.n_devices,
        metrics=metrics_path)

    # Pre-compile every bucket OUTSIDE the timed window so latency numbers
    # measure serving, not XLA compilation.
    t0 = time.monotonic()
    compiled = server.warmup()
    row(event="warmup_done", buckets_compiled=compiled,
        compile_s=round(time.monotonic() - t0, 2))
    server.start()

    rng = np.random.default_rng(0)
    obs_pool = rng.integers(0, 255, (64, 44, 44, 2), dtype=np.uint8)
    issued = threading.Semaphore(args.requests)  # total-request budget
    completed = [0]
    completed_lock = threading.Lock()
    swap_at = args.requests // 2
    swap_fired = threading.Event()
    errors = []

    def swap_params():
        """The hot-swap under load: perturbed params in, zero dropped
        requests expected (verified post-hoc from server stats)."""
        perturbed = jax.tree.map(lambda x: x + 0.01, state.params)
        version = server.load_params(perturbed)
        row(event="swap_fired", at_request=swap_at, params_version=version)

    def client(idx: int):
        while issued.acquire(blocking=False):
            try:
                server.act(obs_pool[idx % len(obs_pool)], timeout=120)
            except Exception as e:  # noqa: BLE001 — report, don't hang the bench
                errors.append(f"{type(e).__name__}: {e}")
                return
            should_swap = False
            with completed_lock:
                completed[0] += 1
                if not args.no_swap and completed[0] >= swap_at \
                        and not swap_fired.is_set():
                    swap_fired.set()
                    should_swap = True
            if should_swap:
                # the device_put runs OUTSIDE the lock — holding it would
                # stall every other client's completion path and charge the
                # swap's cost to the measured latency as harness contention
                swap_params()

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(args.clients)
    ]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.monotonic() - t_start
    stats = server.stop()

    occupancy = stats["batch_occupancy_lifetime"]
    rps = completed[0] / max(wall_s, 1e-9)
    row(metric="serve_batch_occupancy_mean", value=occupancy, unit="req/batch")
    for k in ("latency_p50_ms", "latency_p95_ms", "latency_p99_ms"):
        if k in stats:
            row(metric=f"serve_{k}", value=stats[k], unit="ms")
    row(metric="serve_shed_total", value=stats["total_shed"], unit="requests")
    row(metric="serve_swaps", value=stats["total_swaps"], unit="events")
    if errors:
        row(event="client_errors", n=len(errors), first=errors[0])
        return 1
    if completed[0] != args.requests:
        row(event="incomplete", completed=completed[0], expected=args.requests)
        return 1
    # Blocking clients can hold at most `clients` requests in flight, so any
    # shed below the queue bound is a server bug, not an overload.
    if args.clients <= args.queue_bound and stats["total_shed"] > 0:
        row(event="unexpected_shed", shed=stats["total_shed"])
        return 1
    # The coalescing gate from the docstring and docs/SERVING.md, enforced:
    # at 64+ clients a healthy batcher runs far above 4 requests/batch, and
    # occupancy ~1 means micro-batching silently stopped working.
    if args.clients >= 64 and occupancy <= 4:
        row(event="occupancy_below_gate", occupancy=occupancy, gate=4)
        return 1
    row(metric="serve_requests_per_sec", value=round(rps, 1), unit="req/s",
        requests=completed[0], wall_s=round(wall_s, 2),
        occupancy=occupancy, path="in_process")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
