#!/usr/bin/env python
"""chaos_soak: drive the elastic fleet layer through a seeded kill/revive
schedule with REAL processes, and assert the run heals (docs/RESILIENCE.md).

    python scripts/chaos_soak.py --frames 2000 --kill-schedule seeded
    python scripts/chaos_soak.py --frames 600 --out /tmp/soak --json

Topology (everything jax-free, so the soak runs anywhere in seconds):

    parent = learner + elastic controller          actor children (one per
      ShardedReplay (one shard per actor host)       host, respawnable)
      WeightMailbox.publish_params             --->  MailboxSubscriber.poll
        (int8-delta payloads, PR-8 codec)             (bit-exact adopt +
                                                       StalenessFence)
      spool ingest (epoch-fenced append_shard) <---  spool JSONL rows
      HeartbeatMonitor.poll (lease edges)      <---  HeartbeatWriter lease
      RoleSupervisor (respawn w/ backoff, FailureBudget eviction)

Weight distribution is the REAL quantized consumer path (utils/quantize.py
delta codec behind ``--publish-compression int8_delta``, the default):
every publish ships an int8 delta (periodic full base), children hold a
stateful `MailboxSubscriber` and log each adoption's version + params
checksum; the harness asserts every adopted checksum matches the
publisher's own reconstruction (bit-exactness across processes), that the
slow adopter applied multi-packet chains (gap adoption), and that the
REVIVED incarnation's fresh subscriber late-joined through base+delta
chain replay — the PR-8 follow-up, exercised under kill/revive.  Children
also carry a per-host ``game`` label in their lease payload and fence rows
(the multitask game-aware lease contract, docs/MULTITASK.md).

Seeded schedule (`--kill-schedule seeded`): host 1 is killed mid-run via the
``actor_exit`` fault point and REVIVED — the supervisor respawns it at lease
epoch+1, its lease edge fires ``host_alive``, its shard is readmitted
(``shard_readmit``), and its leftover epoch-0 spool rows are rejected by the
epoch fence.  Host 2 is killed and every respawn is poisoned, so the
FailureBudget exhausts and it is permanently evicted (``actor_evicted``).
Host 3 lives but adopts weights slowly, so the staleness fence pauses it
(``actor_fenced``) instead of letting it act past ``max_weight_lag``.  The
``lease_lost`` point briefly suppresses host 3's renewals (below the death
timeout), and ``shard_rejoin`` makes the first readmission attempt fail so
the retry path runs.

The harness asserts, from its own JSONL (exit 0 only if ALL hold):
  * the final health row is ``status=ok`` (the run HEALED, not just survived);
  * a ``shard_readmit`` row exists and a post-readmit sample drew from the
    readmitted shard;
  * the unrevived host was evicted after its FailureBudget;
  * no actor row ever acted with ``weight_version_lag > max_weight_lag``;
  * stale-epoch spool rows were fenced (``fenced_writes > 0``);
  * the whole run dir lints against the obs/ schema (strict JSON).

`make soak-smoke` runs this at --frames 2000; the `chaos`-marked tier-1 test
(tests/test_elastic.py) runs a smaller budget.

Learner failover (`--kill-learner`, `make failover-smoke`): a second
topology exercising parallel/failover.py with real processes — a jax-free
toy learner child (deterministic per-step state evolution, CRC'd toy
checkpoints, real `WeightMailbox.publish_params` stamped with its claimed
learner epoch, a `learner`-role lease) is SIGKILLed mid-run while a live
standby child (`StandbyLearner` with an injected toy-restore takeover)
tails its lease.  The parent deliberately tears the newest toy checkpoint
(the write the learner died mid-way through) so the takeover must restore
PAST it.  Gates: the standby claims within the lease timeout (plus
detection cadence), mailbox weight versions are strictly monotone across
the takeover, zero stale adoptions (every adoption digest-checked against
the publisher's own reconstruction), the successor's post-takeover state
is bitwise equal to a plain kill->resume replay from the same checkpoint,
and the whole run dir lints.  Emits one report-only ``failover_mttr`` row.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from rainbow_iqn_apex_tpu.netcore import chaos as netchaos  # noqa: E402
from rainbow_iqn_apex_tpu.utils import faults  # noqa: E402

FRAME = 8  # tiny synthetic frames: the soak exercises plumbing, not learning
LANES = 2  # env lanes per actor host
GAMES = ("toy:catch", "toy:chain")  # per-host game labels (round-robin):
# the lease/fence game-attribution contract, not real envs — the soak
# exercises plumbing


def params_digest(params) -> str:
    """Deterministic cross-process digest of a {name: ndarray} pytree —
    the bit-exactness yardstick for publisher vs subscriber reconstruction."""
    import hashlib

    h = hashlib.sha1()
    for name in sorted(params):
        arr = np.ascontiguousarray(np.asarray(params[name], np.float32))
        h.update(name.encode())
        h.update(arr.tobytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------- actor child
def actor_main(args) -> int:
    """One actor host: lease renewal, weight adoption + staleness fence,
    spool production.  Deliberately jax-free (~0.3s cold start)."""
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatWriter,
        MailboxSubscriber,
        StalenessFence,
        WeightMailbox,
    )
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    if args.poison:
        return 1  # a crash-looping binary: dies before it ever leases

    injector = faults.FaultInjector(
        os.environ.get(faults.ENV_VAR, ""), seed=args.seed
    )
    hb_dir = os.path.join(args.dir, "heartbeats")
    lease = HeartbeatWriter(
        hb_dir, args.host, args.hb_interval, injector=injector,
        role="actor", shard=args.shard, epoch=args.epoch,
    )
    if args.game:  # multi-game lease payload field (Lease.game)
        lease.update_payload(game=args.game)
    lease.start()
    metrics = MetricsLogger(
        os.path.join(args.dir, f"actor_h{args.host}_e{args.epoch}.jsonl"),
        run_id=args.run_id, echo=False, host=args.host,
    )
    fence = StalenessFence(args.max_weight_lag, metrics=metrics,
                           game=args.game or None)
    mailbox = WeightMailbox(os.path.join(args.dir, "weights.json"))
    # the quantized consumer path (PR-8 delta codec): a fresh incarnation's
    # subscriber late-joins via base+delta chain replay; an in-sync one
    # tail-applies only the new deltas.  Every adoption logs the
    # reconstruction digest the harness checks against the publisher's.
    subscriber = MailboxSubscriber(mailbox)
    spool_path = os.path.join(
        args.dir, "spool", f"h{args.host}_e{args.epoch}.jsonl"
    )
    os.makedirs(os.path.dirname(spool_path), exist_ok=True)
    rng = np.random.default_rng(args.seed + 101 * args.host + args.epoch)
    held = -1
    produced = 0
    with open(spool_path, "a", buffering=1) as spool:
        for tick in range(1, args.max_ticks + 1):
            if injector.enabled and injector.fire("actor_exit"):
                metrics.log("fault", event="actor_exit", tick=tick)
                metrics.close()
                os._exit(3)  # the kill: no flush, no lease farewell
            published = mailbox.version()
            if held < 0 or tick % args.adopt_every == 0:
                prev = subscriber.version
                row = mailbox.read() or {}
                params = subscriber.poll()
                if params is not None:
                    held = subscriber.version
                    lease.set_weight_version(held)
                    metrics.log(
                        "adopt", tick=tick, version=held,
                        prev_version=prev,
                        checksum=params_digest(params),
                        chain_len=len(row.get("chain") or ()),
                        resyncs=subscriber.resyncs,
                    )
                elif "chain" not in row and published >= 0:
                    # plain version-row mailbox (no payload published):
                    # fall back to the PR-4 version-only adoption so the
                    # fence arithmetic still runs
                    held = published
                    lease.set_weight_version(held)
            acted = fence.observe(
                held, published, step=tick, frames_at_stake=LANES
            )
            # the lease carries the fence state, so the learner-side
            # controller (and its RunHealth) sees a fenced actor without
            # tailing this process's local JSONL
            lease.payload["fenced"] = fence.fenced
            if acted and published >= 0:
                row = {
                    "epoch": args.epoch,
                    "tick": tick,
                    "weight_version": held,
                    "f": rng.integers(0, 255, (LANES, FRAME, FRAME)).tolist(),
                    "a": rng.integers(0, 4, LANES).tolist(),
                    "r": np.round(rng.normal(size=LANES), 4).tolist(),
                    "d": (rng.random(LANES) < 0.05).tolist(),
                }
                spool.write(json.dumps(row) + "\n")
                produced += 1
            if tick % 25 == 0 or not acted:
                metrics.log(
                    "actor", tick=tick, acted=bool(acted), lag=fence.lag,
                    weight_version=held, produced=produced,
                    shed_frames=fence.shed_frames,
                )
            time.sleep(args.tick_s)
    lease.stop()
    metrics.close()
    return 0


# ------------------------------------------------------------- learner parent
class SpoolIngestor:
    """Tail every spool file for a shard; feed rows through the epoch fence.

    Ingest is deliberately throttled (``max_rows`` per poll) so a killed
    host leaves unconsumed rows behind — exactly the at-least-once leftovers
    the epoch fence must reject after readmission."""

    def __init__(self, spool_dir: str, memory, max_rows: int = 1):
        self.spool_dir = spool_dir
        self.memory = memory
        self.max_rows = max_rows
        self._offsets: dict = {}  # path -> byte offset consumed

    def poll_shard(self, shard: int, host: int) -> int:
        """Ingest up to ``max_rows`` spool rows for ``shard``; returns the
        number of transitions ACCEPTED by the fence."""
        accepted = 0
        try:
            names = sorted(os.listdir(self.spool_dir))
        except FileNotFoundError:
            return 0
        budget = self.max_rows
        for name in names:
            if budget <= 0:
                break
            if not name.startswith(f"h{host}_e") or not name.endswith(".jsonl"):
                continue
            path = os.path.join(self.spool_dir, name)
            off = self._offsets.get(path, 0)
            with open(path) as f:
                f.seek(off)
                while budget > 0:
                    line = f.readline()
                    if not line or not line.endswith("\n"):
                        break  # EOF or a row mid-write; retry next poll
                    off = f.tell()
                    budget -= 1
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue  # torn row: skip, never wedge the learner
                    ok = self.memory.append_shard(
                        shard,
                        np.asarray(row["f"], np.uint8),
                        np.asarray(row["a"], np.int32),
                        np.asarray(row["r"], np.float32),
                        np.asarray(row["d"], bool),
                        epoch=int(row.get("epoch", 0)),
                    )
                    if ok:
                        accepted += len(row["a"])
            self._offsets[path] = off
        return accepted


def soak_main(args) -> int:
    from rainbow_iqn_apex_tpu.obs.health import RunHealth
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatMonitor,
        MailboxSubscriber,
        RoleSupervisor,
        WeightMailbox,
    )
    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    run_id = f"soak_{args.seed}"
    run_dir = os.path.join(args.out, "results", run_id)
    os.makedirs(run_dir, exist_ok=True)
    hb_dir = os.path.join(run_dir, "heartbeats")
    spool_dir = os.path.join(run_dir, "spool")
    hosts = list(range(1, args.actors + 1))  # parent is host 0
    shard_of = {h: h - 1 for h in hosts}

    metrics = MetricsLogger(
        os.path.join(run_dir, "metrics.jsonl"), run_id=run_id,
        echo=not args.quiet, host=0,
    )
    registry = MetricRegistry()
    health = RunHealth(registry, metrics, role="soak")
    metrics.add_observer(health.observe_row)

    if args.net:
        # --net composition: arm the seeded network-fault interposer on
        # every socket the parent opens, alongside the process-kill
        # schedule.  Children get the same spec via env (site = their
        # role label) in spawn() below.
        armed = netchaos.install(
            netchaos.NetChaos(args.net, seed=args.seed, site="soak-parent"))
        armed.attach_logger(metrics)

    memory = ShardedReplay.build(
        args.actors, args.actors * 2048, args.actors * LANES,
        frame_shape=(FRAME, FRAME), history=1, n_step=1, gamma=0.9,
        seed=args.seed,
    )
    memory.attach_registry(registry)
    ingest = SpoolIngestor(spool_dir, memory)
    # host= stamps pub_host into every row: subscribers rebuild the
    # publisher's "w<host>-<version>" trace id from it, so a non-zero-host
    # controller must pass its own id or cross-process publish->adopt flow
    # arrows never join (this soak's controller IS host 0)
    mailbox = WeightMailbox(
        os.path.join(run_dir, "weights.json"), host=0,
        base_interval=args.publish_base_interval,
        compression=args.publish_compression,
    )
    monitor = HeartbeatMonitor(hb_dir, args.hb_timeout, self_id=0)
    # the published weights: a tiny pytree the parent perturbs per publish.
    # A REFERENCE subscriber (same decode path the children run) records
    # each version's reconstruction digest — the bit-exactness ground truth
    # the children's adopt rows are asserted against.
    prng = np.random.default_rng(args.seed + 7)
    learner_params = {
        "w": prng.standard_normal((8, 8)).astype(np.float32),
        "b": prng.standard_normal(8).astype(np.float32),
    }
    ref_sub = MailboxSubscriber(mailbox)
    published_digests: dict = {}  # version -> reconstruction digest

    def publish_weights(v: int, step: int) -> None:
        for name in learner_params:
            learner_params[name] = (
                learner_params[name]
                + 0.01 * prng.standard_normal(
                    learner_params[name].shape).astype(np.float32))
        mailbox.publish_params(dict(learner_params), v, step=step)
        ref = ref_sub.poll()
        if ref is not None:
            published_digests[v] = params_digest(ref)

    # the first readmission attempt fails (shard_rejoin point) so the
    # retry path is part of every soak, not just the happy path
    faults.install(faults.FaultInjector("shard_rejoin@1", seed=args.seed))

    # seeded kill schedule: deterministic child-side actor_exit ticks
    rng = np.random.default_rng(args.seed)
    seeded = args.kill_schedule == "seeded"
    revive_host = hosts[0] if seeded else None
    poison_host = hosts[1] if seeded and len(hosts) > 1 else None
    kill_tick = {}
    if seeded:
        kill_tick[revive_host] = int(120 + rng.integers(0, 40))
        if poison_host is not None:
            kill_tick[poison_host] = int(160 + rng.integers(0, 40))
    slow_host = hosts[-1]  # slow weight adoption: the fence's customer

    def spawn_host(host: int):
        def spawn(epoch: int):
            import subprocess

            argv = [
                sys.executable, os.path.abspath(__file__), "--actor",
                "--dir", run_dir, "--run-id", run_id,
                "--host", str(host), "--shard", str(shard_of[host]),
                "--epoch", str(epoch), "--seed", str(args.seed),
                "--hb-interval", str(args.hb_interval),
                "--max-weight-lag", str(args.max_weight_lag),
                "--adopt-every",
                str(40 if host == slow_host else 3),
                # per-host game label (multitask lease contract): rides the
                # lease payload + fence rows so the controller stays
                # game-aware without tailing actor JSONL
                "--game", GAMES[(host - 1) % len(GAMES)],
                # children tick twice as fast as the throttled ingest, so a
                # killed host always leaves unconsumed spool rows behind for
                # the epoch fence to reject after readmission
                "--tick-s", str(args.tick_s / 2),
                "--max-ticks", "100000",
            ]
            env = dict(os.environ)
            env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
            spec = []
            if epoch == 0 and host in kill_tick:
                spec.append(f"actor_exit@{kill_tick[host]}")
            if host == slow_host:
                # a short renewal gap, below the death timeout: the point
                # fires without manufacturing a false-positive drop
                spec.append("lease_lost@8,lease_lost@9")
            if epoch > 0 and host == poison_host:
                argv.append("--poison")  # crash loop: budget must exhaust
            env[faults.ENV_VAR] = ",".join(spec)
            if args.net:
                env[netchaos.ENV_VAR] = args.net
                env[netchaos.SEED_ENV_VAR] = str(args.seed)
                env[netchaos.SITE_ENV_VAR] = f"actor{host}"
            return subprocess.Popen(argv, env=env,
                                    stdout=subprocess.DEVNULL,
                                    stderr=subprocess.STDOUT)

        return spawn

    from rainbow_iqn_apex_tpu.config import Config

    sup = RoleSupervisor.from_config(
        Config(respawn_attempts=args.respawn_attempts,
               respawn_base_s=args.respawn_base_s,
               respawn_max_s=2 * args.respawn_base_s,
               seed=args.seed),
        metrics=metrics, registry=registry,
    )
    for h in hosts:
        sup.register(f"actor_h{h}", spawn_host(h), epoch=0,
                     meta={"role_host": h})

    version = 1
    publish_weights(version, step=0)
    frames = 0
    step = 0
    readmitted: dict = {}  # host -> readmit epoch
    fenced_state: dict = {}  # host -> last lease-reported fence state
    post_readmit_draw = False
    deadline = time.monotonic() + args.deadline_s
    last_health = {"status": "none"}
    samples = 0

    def relay_fence_edges() -> bool:
        """Emit fence/resume edges off fresh leases into the parent's
        metrics funnel (where RunHealth observes them); returns True while
        any live actor is still fenced."""
        any_fenced = False
        for hid, lease in monitor.leases().items():
            if not (lease.fresh and lease.payload_ok):
                continue
            if lease.fenced != fenced_state.get(hid, False):
                fenced_state[hid] = lease.fenced
                metrics.log(
                    "actor_fenced",
                    action="fence" if lease.fenced else "resume",
                    fenced_host=hid,
                    lag=max(version - lease.weight_version, 0),
                    max_lag=args.max_weight_lag, step=step,
                )
            any_fenced |= lease.fenced
        return any_fenced

    def story_done() -> bool:
        if not seeded:  # no-kill soak: the frame budget is the whole story
            return frames >= args.frames
        evicted_ok = poison_host is None or f"actor_h{poison_host}" in sup.evicted()
        return (
            frames >= args.frames
            and revive_host in readmitted
            and evicted_ok
            and post_readmit_draw
            and memory.fenced_writes > 0
            and sup.all_settled()
        )

    try:
        tick = 0
        while not story_done() and time.monotonic() < deadline:
            tick += 1
            # 1. ingest: every live shard's spool, epoch-fenced
            for h in hosts:
                k = shard_of[h]
                if k in memory.dead_shards:
                    continue
                frames += ingest.poll_shard(k, h)
            # 2. "learn": sample + priority write-back once warm
            if len(memory) >= args.learn_start and memory.sampleable:
                step += 1
                batch = memory.sample(16, beta=0.6)
                memory.update_priorities(
                    batch.idx, np.abs(rng.normal(size=len(batch.idx))) + 0.1
                )
                samples += 1
                if revive_host in readmitted:
                    lo = shard_of[revive_host] * memory.shard_capacity
                    hi = lo + memory.shard_capacity
                    if ((batch.idx >= lo) & (batch.idx < hi)).any():
                        post_readmit_draw = True
                if step % args.publish_every == 0:
                    version += 1
                    publish_weights(version, step=step)
                    registry.gauge("weights_version", "soak").set(version)
            # 3. lease edges -> degrade / heal
            dead, alive = monitor.poll()
            for lease in dead:
                k = shard_of.get(lease.host)
                metrics.log("fault", event="host_dead", dead_host=lease.host,
                            epoch=lease.epoch, step=step, frames=frames)
                if fenced_state.pop(lease.host, False):
                    # the fence died with its incarnation; close the episode
                    # so a kill mid-fence can't hold health degraded forever
                    metrics.log("actor_fenced", action="resume",
                                fenced_host=lease.host, lag=0,
                                max_lag=args.max_weight_lag, step=step)
                if k is not None and k not in memory.dead_shards:
                    try:
                        memory.drop_shard(k)
                    except RuntimeError:
                        pass  # never drop the last survivor
            for lease in alive:
                k = shard_of.get(lease.host)
                metrics.log("host_alive", alive_host=lease.host,
                            epoch=lease.epoch, step=step, frames=frames)
                if k is None or k not in memory.dead_shards:
                    continue
                epoch = faults.retry_call(
                    lambda: memory.readmit_shard(k, epoch=lease.epoch),
                    faults.RetryPolicy(attempts=3, base_delay_s=0.01,
                                       max_delay_s=0.05, seed=args.seed),
                    retry_on=(OSError,),
                    on_retry=lambda att, e: metrics.log(
                        "fault", event="shard_rejoin_retry", attempt=att,
                        shard=k, error=str(e)[:120]),
                )
                readmitted[lease.host] = epoch
                metrics.log("shard_readmit", shard=k, epoch=epoch,
                            step=step, frames=frames)
            # 4. fence edges relayed off the leases: RunHealth holds the run
            # degraded while any live actor is fenced, without the learner
            # tailing actor-local JSONL
            relay_fence_edges()
            # 5. respawn supervision (emits actor_dead/respawn/evicted rows)
            sup.poll(step=step)
            # 6. periodic health
            if tick % 25 == 0:
                last_health = health.tick(
                    step, frames, replay_size=len(memory),
                    dead_shards=list(memory.dead_shards),
                    fenced_writes=memory.fenced_writes,
                )
            time.sleep(args.tick_s)
        # final settle: publishing has stopped, so a still-fenced slow
        # adopter unfences within one adoption interval — wait for the live
        # fences to clear (bounded), flush the window holding the last heal
        # events (it may legitimately read degraded), then close one CLEAN
        # window — a healed run must end ok, and a still-broken one must not
        settle_deadline = time.monotonic() + 5.0
        while relay_fence_edges() and time.monotonic() < settle_deadline:
            time.sleep(args.tick_s)
        health.tick(step, frames)
        time.sleep(args.tick_s)
        monitor.poll()
        last_health = health.tick(
            step + 1, frames, replay_size=len(memory),
            dead_shards=list(memory.dead_shards),
            fenced_writes=memory.fenced_writes,
        )
    finally:
        sup.stop_all()
        metrics.close()
        faults.install(None)  # don't leak the soak's injector to callers

    # ----------------------------------------------------- harness assertions
    failures = []
    if last_health.get("status") != "ok":
        failures.append(f"final health is {last_health.get('status')!r}, "
                        f"not 'ok' ({last_health})")
    if frames < args.frames:
        failures.append(f"only {frames}/{args.frames} frames ingested "
                        "before the deadline")
    if seeded:
        if revive_host not in readmitted:
            failures.append(f"host {revive_host} was never readmitted")
        if not post_readmit_draw:
            failures.append(
                "no post-readmit sample drew from the revived shard")
        if (poison_host is not None
                and f"actor_h{poison_host}" not in sup.evicted()):
            failures.append(f"host {poison_host} was not evicted")
        if memory.fenced_writes <= 0:
            failures.append("epoch fence never rejected a stale spool row")

    # fence law, asserted from the actors' OWN rows: an actor may lag, but
    # must never ACT past the budget.  The same sweep collects the
    # subscriber adoptions (the quantized consumer path's evidence).
    fence_rows = 0
    fence_rows_with_game = 0
    adopt_rows = []  # (file, row) for every subscriber adoption
    for name in sorted(os.listdir(run_dir)):
        if not (name.startswith("actor_h") and name.endswith(".jsonl")):
            continue
        for line in open(os.path.join(run_dir, name)):
            try:
                row = json.loads(line)
            except ValueError:
                failures.append(f"{name}: non-JSON actor row")
                continue
            if row.get("kind") == "actor" and row.get("acted"):
                if int(row.get("lag", 0)) > args.max_weight_lag:
                    failures.append(
                        f"{name}: acted with lag {row['lag']} > "
                        f"{args.max_weight_lag}")
            if row.get("kind") == "actor_fenced":
                fence_rows += 1
                if row.get("game"):
                    fence_rows_with_game += 1
            if row.get("kind") == "adopt":
                adopt_rows.append((name, row))
    if seeded and fence_rows == 0:
        failures.append("no actor_fenced row: the staleness fence never "
                        "exercised")
    if seeded and fence_rows_with_game == 0:
        failures.append("no actor_fenced row carried its game label (the "
                        "game-aware lease/fence contract broke)")

    # quantized consumer path (PR-8 follow-up): every adoption any child
    # reported must be BIT-EXACT with the publisher's own reconstruction
    # for that version, the slow adopter must have applied multi-packet
    # chains (gap adoption), and the revived incarnation's fresh
    # subscriber must have late-joined through base+delta chain replay
    if not adopt_rows:
        failures.append("no subscriber adoption: the quantized mailbox "
                        "consumer path never ran")
    for name, row in adopt_rows:
        want = published_digests.get(int(row["version"]))
        if want is None:
            failures.append(f"{name}: adopted unpublished version "
                            f"{row['version']}")
        elif row.get("checksum") != want:
            failures.append(
                f"{name}: adoption of v{row['version']} not bit-exact "
                f"({row.get('checksum')} != {want})")
    if args.publish_compression == "int8_delta" and adopt_rows:
        if not any(int(r["version"]) - int(r.get("prev_version", -1)) > 1
                   for _n, r in adopt_rows):
            failures.append("no multi-packet chain adoption (every adopt "
                            "was a single-delta tail apply)")
        if seeded and revive_host in readmitted:
            revived = [r for n, r in adopt_rows
                       if n.startswith(f"actor_h{revive_host}_e")
                       and not n.endswith("_e0.jsonl")]
            if not any(int(r.get("prev_version", 0)) < 0 for r in revived):
                failures.append(
                    f"revived host {revive_host} never late-joined via "
                    "base+delta chain replay (no fresh-subscriber adopt)")
    if seeded and registry.counter("actor_fenced_total", "health").get() == 0:
        failures.append("RunHealth never observed a fence episode (the "
                        "lease-carried fence relay broke)")

    # the run dir must lint against the obs schema (the three new row kinds
    # included) — a soak that heals but emits unparseable telemetry failed
    from scripts.lint_jsonl import lint_file  # noqa: E402

    lint_errors = []
    for name in sorted(os.listdir(run_dir)):
        if name.endswith(".jsonl"):
            lint_errors += lint_file(os.path.join(run_dir, name))
    if lint_errors:
        failures.append(f"lint errors: {lint_errors[:5]}")

    summary = {
        "ok": not failures,
        "frames": frames,
        "learn_steps": step,
        "samples": samples,
        "weights_version": version,
        "readmitted": {str(h): e for h, e in readmitted.items()},
        "evicted": sup.evicted(),
        "fenced_writes": memory.fenced_writes,
        "fence_rows": fence_rows,
        "adoptions": len(adopt_rows),
        "adopt_resyncs": max(
            (int(r.get("resyncs", 0)) for _n, r in adopt_rows), default=0),
        "publish_compression": args.publish_compression,
        "final_health": last_health.get("status"),
        "failures": failures,
    }
    with open(os.path.join(run_dir, "soak_summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    out = json.dumps(summary, indent=2) if args.json else (
        f"chaos_soak: {'OK' if summary['ok'] else 'FAILED'} "
        f"frames={frames} readmitted={summary['readmitted']} "
        f"evicted={summary['evicted']} fenced={memory.fenced_writes} "
        f"health={summary['final_health']}"
        + ("".join(f"\n  FAIL {f}" for f in failures))
    )
    print(out)
    return 0 if summary["ok"] else 1


# ------------------------------------------------------- learner failover
# A toy learner whose whole state is a pure function of (checkpoint, step):
# each step perturbs the params with a PER-STEP seeded rng, so replaying
# from any checkpoint reproduces the exact bytes — the yardstick for the
# "post-takeover step bitwise equal to plain kill->resume" gate.


def toy_params(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"b": rng.standard_normal(8).astype(np.float32),
            "w": rng.standard_normal((8, 8)).astype(np.float32)}


def toy_step(params: dict, step: int, seed: int) -> None:
    rng = np.random.default_rng(seed * 1_000_003 + step)
    for name in sorted(params):
        params[name] = (params[name] + 0.01 * rng.standard_normal(
            params[name].shape).astype(np.float32))


def toy_save(run_dir: str, step: int, params: dict) -> str:
    """Atomic digest-stamped toy checkpoint (tmp+rename; float32 round-trips
    json exactly, so restore is bitwise)."""
    d = os.path.join(run_dir, "toyckpt")
    os.makedirs(d, exist_ok=True)
    body = {"step": int(step),
            "digest": params_digest(params),
            "params": {k: np.asarray(v, np.float32).tolist()
                       for k, v in sorted(params.items())}}
    path = os.path.join(d, f"ck_{step:08d}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(body, f)
    os.replace(tmp, path)
    return path


def toy_restore(run_dir: str):
    """Newest VALID toy checkpoint, scanning past torn/corrupt newer files —
    the `Checkpointer.restore_latest_valid` contract in miniature."""
    d = os.path.join(run_dir, "toyckpt")
    try:
        names = sorted(os.listdir(d), reverse=True)
    except FileNotFoundError:
        return None
    for name in names:
        if not name.startswith("ck_") or not name.endswith(".json"):
            continue
        path = os.path.join(d, name)
        try:
            with open(path) as f:
                body = json.load(f)
            params = {k: np.asarray(v, np.float32)
                      for k, v in body["params"].items()}
            if params_digest(params) != body["digest"]:
                continue  # corrupt payload: keep scanning older
            return {"step": int(body["step"]), "params": params,
                    "path": path}
        except (OSError, ValueError, KeyError):
            continue  # torn file: keep scanning older
    return None


def _toy_cfg(args):
    from rainbow_iqn_apex_tpu.config import Config

    return Config(
        results_dir=os.path.dirname(args.dir),
        run_id=os.path.basename(args.dir),
        seed=args.seed,
        failover_standby=True,
        failover_poll_s=max(args.tick_s, 0.02),
        heartbeat_interval_s=args.hb_interval,
        heartbeat_timeout_s=args.hb_timeout,
        process_id=args.host,
    )


def learner_main(args) -> int:
    """The toy learner child: claims a learner-role epoch through the REAL
    O_EXCL markers, leases as role=learner, publishes epoch-stamped params
    through the real mailbox, checkpoints every --ckpt-every steps."""
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        HeartbeatWriter,
        MailboxSubscriber,
        StaleEpochError,
        WeightMailbox,
    )
    from rainbow_iqn_apex_tpu.parallel.failover import (
        LEARNER_ROLE,
        learner_epoch_at_start,
        mailbox_path,
    )
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    cfg = _toy_cfg(args)
    injector = faults.FaultInjector(
        os.environ.get(faults.ENV_VAR, ""), seed=args.seed)
    epoch = learner_epoch_at_start(cfg)
    hb = HeartbeatWriter(
        os.path.join(args.dir, "heartbeats"), args.host, args.hb_interval,
        role=LEARNER_ROLE,
    )
    hb.update_payload(learner_epoch=epoch)
    hb.start()
    metrics = MetricsLogger(
        os.path.join(args.dir, f"learner_e{epoch}.jsonl"),
        run_id=args.run_id, echo=False, host=args.host,
    )
    metrics.log("failover", event="claim", won=True, epoch=epoch,
                source="learner_start")
    mailbox = WeightMailbox(mailbox_path(cfg), host=args.host)
    # the publisher's own reference reconstruction (same decode path every
    # consumer runs) is the digest ground truth the harness checks against
    ref_sub = MailboxSubscriber(mailbox)
    restored = toy_restore(args.dir)
    step = restored["step"] if restored else 0
    params = restored["params"] if restored else toy_params(args.seed)
    version = mailbox.version()  # disk floor: strictly above any predecessor
    rc = 0
    for _ in range(args.max_ticks):
        if injector.enabled and injector.fire("learner_exit"):
            metrics.log("fault", event="learner_exit", step=step)
            metrics.close()
            os._exit(3)  # the kill: no flush, no lease farewell
        step += 1
        toy_step(params, step, args.seed)
        if step % args.ckpt_every == 0:
            toy_save(args.dir, step, params)
        if step % args.publish_every == 0:
            version += 1
            try:
                row = mailbox.publish_params(
                    dict(params), version, step=step, learner_epoch=epoch)
            except StaleEpochError:
                # a successor claimed a higher epoch while this learner was
                # paused: the zombie fence — refuse to clobber, stand down
                metrics.log("failover", event="fenced_stale",
                            surface="mailbox", epoch=epoch)
                rc = 4
                break
            ref = ref_sub.poll()
            metrics.log("publish", version=version, step=step,
                        bytes=int(row.get("bytes", 0) or 0),
                        digest=params_digest(ref) if ref is not None
                        else None,
                        epoch=epoch)
        time.sleep(args.tick_s)
    hb.stop()
    metrics.close()
    return rc


def standby_child_main(args) -> int:
    """The standby child: a REAL `StandbyLearner` tailing the learner's
    lease, with the jax-heavy takeover replaced by the toy restore+replay
    (the injected-callback seam run_standby documents for harnesses)."""
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        MailboxSubscriber,
        StaleEpochError,
        WeightMailbox,
    )
    from rainbow_iqn_apex_tpu.parallel.failover import (
        StandbyLearner,
        mailbox_path,
    )
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    cfg = _toy_cfg(args)
    faults.install(faults.FaultInjector(
        os.environ.get(faults.ENV_VAR, ""), seed=args.seed))
    metrics = MetricsLogger(
        os.path.join(args.dir, f"standby_h{args.host}.jsonl"),
        run_id=args.run_id, echo=False, host=args.host,
    )
    mailbox = WeightMailbox(mailbox_path(cfg), host=args.host)
    ref_sub = MailboxSubscriber(mailbox)

    def takeover(epoch: int, warm_params):
        # restore the newest VALID toy checkpoint (scanning past the
        # parent's deliberately torn newest), replay the deterministic
        # evolution forward, publish strictly above the predecessor with
        # the NEW learner epoch stamped
        restored = toy_restore(args.dir)
        step = restored["step"] if restored else 0
        params = (restored["params"] if restored
                  else toy_params(args.seed))
        version = mailbox.version()
        fenced = 0
        for _ in range(args.post_steps):
            step += 1
            toy_step(params, step, args.seed)
            if step % args.ckpt_every == 0:
                toy_save(args.dir, step, params)
            if step % args.publish_every == 0:
                version += 1
                try:
                    row = mailbox.publish_params(
                        dict(params), version, step=step,
                        learner_epoch=epoch)
                except StaleEpochError:
                    fenced += 1
                    metrics.log("failover", event="fenced_stale",
                                surface="mailbox", epoch=epoch)
                    continue
                ref = ref_sub.poll()
                metrics.log("publish", version=version, step=step,
                            bytes=int(row.get("bytes", 0) or 0),
                            digest=params_digest(ref) if ref is not None
                            else None,
                            epoch=epoch)
            time.sleep(args.tick_s)
        return {"restored_step": restored["step"] if restored else 0,
                "restored_path": restored["path"] if restored else None,
                "final_step": step, "final_version": version,
                "final_digest": params_digest(params), "fenced": fenced}

    standby = StandbyLearner(cfg, takeover, metrics=metrics)
    result = standby.run(max_wait_s=args.deadline_s)
    out = {"takeover": result is not None,
           "claims_lost": standby.claims_lost}
    if result is not None:
        out.update(result)
        if isinstance(result.get("outcome"), dict):
            out.update(result["outcome"])  # flatten for the parent's gates
    tmp = os.path.join(args.dir, f"standby_result_h{args.host}.json.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f, indent=2)
    os.replace(tmp, tmp[:-4])
    metrics.close()
    faults.install(None)
    return 0 if result is not None else 1


def failover_main(args) -> int:
    import signal
    import subprocess

    from rainbow_iqn_apex_tpu.obs.health import RunHealth
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.parallel.elastic import (
        MailboxSubscriber,
        WeightMailbox,
    )
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger

    run_id = f"failover_{args.seed}"
    run_dir = os.path.join(args.out, "results", run_id)
    os.makedirs(run_dir, exist_ok=True)
    metrics = MetricsLogger(
        os.path.join(run_dir, "metrics.jsonl"), run_id=run_id,
        echo=not args.quiet, host=0,
    )
    registry = MetricRegistry()
    health = RunHealth(registry, metrics, role="failover")
    metrics.add_observer(health.observe_row)

    if args.net:
        armed = netchaos.install(
            netchaos.NetChaos(args.net, seed=args.seed, site="soak-parent"))
        armed.attach_logger(metrics)

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    standby_host = 9

    def spawn(flag: str, host: int, spec: str = "") -> "subprocess.Popen":
        argv = [
            sys.executable, os.path.abspath(__file__), flag,
            "--dir", run_dir, "--run-id", run_id,
            "--host", str(host), "--seed", str(args.seed),
            "--hb-interval", str(args.hb_interval),
            "--hb-timeout", str(args.hb_timeout),
            "--tick-s", str(args.tick_s),
            "--publish-every", str(args.publish_every),
            "--ckpt-every", str(args.ckpt_every),
            "--post-steps", str(args.post_steps),
            "--deadline-s", str(args.deadline_s),
            "--max-ticks", "100000",
        ]
        child_env = dict(env)
        child_env[faults.ENV_VAR] = spec
        if args.net:
            child_env[netchaos.ENV_VAR] = args.net
            child_env[netchaos.SEED_ENV_VAR] = str(args.seed)
            child_env[netchaos.SITE_ENV_VAR] = f"host{host}"
        return subprocess.Popen(argv, env=child_env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.STDOUT)

    learner = spawn("--learner", 0)
    # the standby's FIRST claim attempt is poisoned (standby_claim point):
    # the re-arm/re-claim path is part of every smoke, not just the tests
    standby = spawn("--standby-child", standby_host, spec="standby_claim@1")

    mailbox = WeightMailbox(os.path.join(run_dir, "mailbox.json"), host=0)
    sub = MailboxSubscriber(mailbox, consumer="harness")
    version_seq: list = []   # every observed mailbox version change
    adopted: dict = {}       # version -> harness reconstruction digest
    t_kill = None
    kill_version = None
    first_succ_pub_t = None
    result_path = os.path.join(run_dir,
                               f"standby_result_h{standby_host}.json")
    deadline = time.monotonic() + args.deadline_s
    last_health = {"status": "none"}
    try:
        while time.monotonic() < deadline:
            v = mailbox.version()
            if v >= 0 and (not version_seq or v != version_seq[-1]):
                version_seq.append(v)
                if (t_kill is not None and first_succ_pub_t is None
                        and v > (kill_version or -1)):
                    first_succ_pub_t = time.monotonic()
            params = sub.poll()
            if params is not None:
                adopted[sub.version] = params_digest(params)
            if t_kill is None and v >= args.kill_after_version:
                kill_version = v
                metrics.log("fault", event="learner_killed", version=v)
                learner.send_signal(signal.SIGKILL)
                learner.wait()
                t_kill = time.monotonic()
                # tear the newest toy checkpoint — the write the learner
                # died mid-way through; the takeover must restore PAST it
                d = os.path.join(run_dir, "toyckpt")
                names = (sorted(os.listdir(d), reverse=True)
                         if os.path.isdir(d) else [])
                if names:
                    torn = os.path.join(d, names[0])
                    with open(torn, "r+") as f:
                        f.truncate(max(os.path.getsize(torn) // 2, 1))
            if (t_kill is not None and os.path.exists(result_path)
                    and standby.poll() is not None):
                break
            time.sleep(args.tick_s)
        # drain: the successor's last publishes may still be in flight
        for _ in range(20):
            v = mailbox.version()
            if v >= 0 and (not version_seq or v != version_seq[-1]):
                version_seq.append(v)
            params = sub.poll()
            if params is not None:
                adopted[sub.version] = params_digest(params)
            time.sleep(args.tick_s)
        health.tick(0, 0)
        time.sleep(args.tick_s)
        last_health = health.tick(1, 0)
    finally:
        for child in (learner, standby):
            if child.poll() is None:
                child.kill()
                child.wait()
        metrics.close()

    # ------------------------------------------------------------- gates
    failures = []
    res: dict = {}
    if os.path.exists(result_path):
        with open(result_path) as f:
            res = json.load(f)
    if not res.get("takeover"):
        failures.append("standby never took the learner role over")
    if t_kill is None:
        failures.append("the learner was never killed (no publishes seen)")
    mttr_value = (round(first_succ_pub_t - t_kill, 3)
                  if (t_kill is not None and first_succ_pub_t is not None)
                  else None)
    if mttr_value is None:
        failures.append("no successor publish after the kill")
    else:
        # the claim must land within the lease timeout plus detection
        # cadence and the (injected) one-attempt re-arm; the bound is the
        # RESILIENCE.md MTTR decomposition with generous process-start slack
        bound = args.hb_timeout + 10.0
        if mttr_value > bound:
            failures.append(f"kill->first successor publish took "
                            f"{mttr_value}s > {bound}s")
    if any(b <= a for a, b in zip(version_seq, version_seq[1:])):
        failures.append(f"mailbox versions not strictly monotone across "
                        f"takeover: {version_seq}")
    # zero stale adoptions: every version the harness subscriber adopted
    # must match the publisher's own reference reconstruction digest
    published: dict = {}
    for name in sorted(os.listdir(run_dir)):
        if not ((name.startswith("learner_e")
                 or name.startswith("standby_h"))
                and name.endswith(".jsonl")):
            continue
        for line in open(os.path.join(run_dir, name)):
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if row.get("kind") == "publish" and row.get("digest"):
                published[int(row["version"])] = row["digest"]
    if not adopted:
        failures.append("the harness subscriber never adopted any publish")
    for v, digest in sorted(adopted.items()):
        want = published.get(v)
        if want is None:
            failures.append(f"adopted version {v} was never published "
                            "(stale adoption)")
        elif digest != want:
            failures.append(f"adoption of v{v} not bit-exact "
                            f"({digest} != {want})")
    # bitwise gate: plain kill->resume replay from the SAME checkpoint the
    # successor restored must land on the same bytes
    if res.get("takeover"):
        if res.get("restored_path") is None:
            failures.append("the takeover restored no checkpoint (the torn "
                            "newest should have older valid siblings)")
        else:
            with open(res["restored_path"]) as f:
                body = json.load(f)
            replay = {k: np.asarray(vv, np.float32)
                      for k, vv in body["params"].items()}
            for s in range(int(body["step"]) + 1,
                           int(res["final_step"]) + 1):
                toy_step(replay, s, args.seed)
            if params_digest(replay) != res.get("final_digest"):
                failures.append(
                    "post-takeover state diverged from plain kill->resume "
                    f"({params_digest(replay)} != {res.get('final_digest')})")
        if res.get("fenced", 0):
            failures.append(f"the successor's own publishes were fenced "
                            f"{res['fenced']}x (epoch ordering broke)")
    # the standby's injected first-claim failure must have left a reasoned
    # loser row before the re-claim won
    injected_claim_rows = 0
    standby_jsonl = os.path.join(run_dir, f"standby_h{standby_host}.jsonl")
    if os.path.exists(standby_jsonl):
        for line in open(standby_jsonl):
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if (row.get("kind") == "failover" and row.get("event") == "claim"
                    and not row.get("won")
                    and row.get("reason") == "injected_fault"):
                injected_claim_rows += 1
    if res.get("takeover") and injected_claim_rows == 0:
        failures.append("the injected standby_claim failure left no "
                        "reasoned claim row (the re-arm path is silent)")

    from scripts.lint_jsonl import lint_file  # noqa: E402

    lint_errors = []
    for name in sorted(os.listdir(run_dir)):
        if name.endswith(".jsonl"):
            lint_errors += lint_file(os.path.join(run_dir, name))
    if lint_errors:
        failures.append(f"lint errors: {lint_errors[:5]}")

    # report-only row: MTTR is machine-weather, never gated
    bench = {
        "path": "failover_mttr",
        "metric": "failover_mttr_s",
        "value": mttr_value,
        "unit": "s",
        "claim_s": res.get("claim_s"),
        "restore_s": res.get("restore_s"),
        "mttr_detect_s": res.get("mttr_s"),
    }
    if failures:
        bench["status"] = "gate_failed"
    print(json.dumps(bench))
    summary = {
        "ok": not failures,
        "takeover": bool(res.get("takeover")),
        "epoch": res.get("epoch"),
        "mttr_s": mttr_value,
        "claim_s": res.get("claim_s"),
        "restore_s": res.get("restore_s"),
        "versions": version_seq,
        "adoptions": len(adopted),
        "restored_step": res.get("restored_step"),
        "final_step": res.get("final_step"),
        "final_health": last_health.get("status"),
        "failures": failures,
    }
    with open(os.path.join(run_dir, "failover_summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    print(json.dumps(summary, indent=2) if args.json else (
        f"failover_smoke: {'OK' if summary['ok'] else 'FAILED'} "
        f"mttr_s={mttr_value} versions={version_seq} "
        f"adoptions={len(adopted)}"
        + "".join(f"\n  FAIL {f}" for f in failures)))
    return 0 if summary["ok"] else 1


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--frames", type=int, default=2000,
                    help="min transitions ingested before the soak can end")
    ap.add_argument("--kill-schedule", default="seeded",
                    choices=["seeded", "none"])
    ap.add_argument("--net", default="",
                    help="network-chaos spec (netcore/chaos grammar, e.g. "
                         "'delay_ms=30+-20@p=0.5,corrupt_frame@p=0.01'): "
                         "armed in the parent and exported to every spawned "
                         "child via RIA_NET_CHAOS, composing wire faults "
                         "with the process-kill schedule")
    ap.add_argument("--actors", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="/tmp/ria_chaos_soak")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--deadline-s", type=float, default=90.0)
    ap.add_argument("--learn-start", type=int, default=64)
    ap.add_argument("--publish-every", type=int, default=5)
    ap.add_argument("--publish-compression", default="int8_delta",
                    choices=["int8_delta", "off"],
                    help="weight-payload codec: int8_delta (default) ships "
                         "the PR-8 base+delta chain; off ships full bases")
    ap.add_argument("--publish-base-interval", type=int, default=4,
                    help="publishes between full base snapshots (short, so "
                         "revive-time chain replay exercises base+deltas)")
    ap.add_argument("--max-weight-lag", type=int, default=2)
    # respawn knobs default to the Config fields (the single source the
    # docs/RESILIENCE.md table names); the backoff base is raised above the
    # training default because of an ordering constraint: the lease must be
    # declared dead (hb-timeout, polled every tick) BEFORE the respawned
    # incarnation leases back in (respawn-base-s minus jitter, plus child
    # start-up) — otherwise the drop/readmit pair never fires
    from rainbow_iqn_apex_tpu.config import Config as _Config

    _cfg = _Config()
    ap.add_argument("--respawn-attempts", type=int,
                    default=_cfg.respawn_attempts)
    ap.add_argument("--respawn-base-s", type=float,
                    default=max(_cfg.respawn_base_s, 1.0))
    ap.add_argument("--hb-interval", type=float, default=0.05)
    ap.add_argument("--hb-timeout", type=float, default=0.3)
    ap.add_argument("--tick-s", type=float, default=0.01)
    # learner failover smoke (--kill-learner; make failover-smoke)
    ap.add_argument("--kill-learner", action="store_true",
                    help="learner-failover smoke: SIGKILL the toy learner "
                         "mid-run with a live standby and gate the takeover "
                         "(docs/RESILIENCE.md 'learner failover')")
    ap.add_argument("--kill-after-version", type=int, default=4,
                    help="mailbox version at which the learner is killed")
    ap.add_argument("--ckpt-every", type=int, default=2,
                    help=argparse.SUPPRESS)
    ap.add_argument("--post-steps", type=int, default=30,
                    help=argparse.SUPPRESS)
    # internal: actor-child mode
    ap.add_argument("--actor", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--learner", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--standby-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--dir", help=argparse.SUPPRESS)
    ap.add_argument("--run-id", default="soak", help=argparse.SUPPRESS)
    ap.add_argument("--host", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--shard", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--epoch", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--adopt-every", type=int, default=3,
                    help=argparse.SUPPRESS)
    ap.add_argument("--game", default="", help=argparse.SUPPRESS)
    ap.add_argument("--max-ticks", type=int, default=100000,
                    help=argparse.SUPPRESS)
    ap.add_argument("--poison", action="store_true", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.actor:
        return actor_main(args)
    if args.learner:
        return learner_main(args)
    if args.standby_child:
        return standby_child_main(args)
    if args.kill_learner:
        return failover_main(args)
    return soak_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
