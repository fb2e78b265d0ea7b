#!/usr/bin/env python
"""Benchmark: learner throughput at the reference's Atari workload shape.

Prints benchmark rows as JSON lines, each shaped
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "path": ...}
flushed the moment they exist; the LAST line is the headline (consumers keep
the last parseable stdout line).

What is measured: sustained full learn steps/sec at the reference
hyperparameters (batch 32, 84x84x4 uint8 frames, IQN N=N'=64, K=32 double-Q
selection, dueling noisy nets, Adam) — the SURVEY.md §3.4 kernel end-to-end
INCLUDING replay sampling, i.e. what the learner role sustains per step of
the Ape-X loop.  On TPU the headline row is the framework's device-resident
PER learner (replay/device.py: HBM ring; sampling + priority write-back
in-graph, no per-step host transfer — `--role anakin`); a host-feed row
(host-sampled synthetic batch + flat-byte transfer each step) is always
measured first as the diagnostic.  Under ``JAX_PLATFORMS=cpu`` only the
host-feed row runs.

Baseline: the reference learner is a PyTorch 1-GPU process at the same shape.
BASELINE.json records no published number ("published": {}); the documented
reference class (SURVEY.md §6, RECON) is ~75 learn-steps/s for a Rainbow-IQN
GPU learner of that era, so vs_baseline = steps_per_sec / 75.  Re-verify when
reference numbers become available (SURVEY.md §8 item 6).

Process model: one process, on the device JAX gives it.  Every row is stamped
with ``platform``, ``device_kind`` and ``device_count``.  A run that finds no
accelerator fails unless the caller pinned ``JAX_PLATFORMS=cpu`` itself (the
Makefile's ``BENCH_*_ONLY`` smokes), and then every row says ``cpu``; a
device-replay phase that raises ends the run non-zero.

Row budgets (round-6): every micro row (apex_loop, sample_path) runs under
its OWN slice of the run's remaining soft budget (``BENCH_WATCHDOG_SECS``)
via _run_row_budgeted — an overrunning row emits a labelled
{"status": "timeout"} row and the rows behind it still run (the r05 failure
dropped every row after one hang).  The sample_path row measures the device
sample frontier (replay/frontier.py) against the host sum-tree sample path
and carries speedup_vs_host; `make perf-smoke` gates on >= 1.5x.  Every row
carries a ``path`` tag (``host_feed`` vs ``device_replay``) so cross-round
comparisons can tell which measurement the headline represents.
"""

import functools
import json
import os
import sys
import time

# soft budget for the whole run; rows take slices of what is left of it
BUDGET_SECS = float(os.environ.get("BENCH_WATCHDOG_SECS", "480"))


def measure() -> None:
    """Measure on whatever device jax gives us.

    Soft-deadline discipline: every loop that issues device calls checks the
    remaining budget between calls and bails out early, keeping whatever it
    measured."""
    t_start = time.monotonic()

    def left() -> float:
        return BUDGET_SECS - (time.monotonic() - t_start)

    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import (
        Batch,
        build_learn_step,
        init_train_state,
    )

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "bench: JAX found no accelerator (first device is cpu); a CPU run "
            "happens only when the caller sets JAX_PLATFORMS=cpu")
    stamp = {"platform": platform, "device_kind": devices[0].device_kind,
             "device_count": len(devices)}
    print(f"bench: {stamp} t_import={time.monotonic()-t_start:.1f}s",
          file=sys.stderr, flush=True)

    def emit(row: dict) -> None:
        print(json.dumps({**row, **stamp}), flush=True)

    # netchaos mode (make netchaos-smoke / BENCH_NETCHAOS_ONLY=1): only the
    # disarmed-interposer seam-tax row — a framed-socket echo loop
    if os.environ.get("BENCH_NETCHAOS_ONLY") == "1":
        for row in _run_row_budgeted(
            "chaos_overhead", "net_chaos_overhead_frac",
            _measure_chaos_overhead, left, share=0.9,
        ):
            emit(row)
        return

    # perf-smoke mode (make perf-smoke): only the pipeline micro rows
    # (apex_loop at toy size + the sample_path micro-path) — the full
    # Atari-shape learn step takes minutes/step on CPU.  Each row gets its
    # OWN budget slice (r05 regression: one overrunning row must not eat
    # the rows behind it).
    # trace-smoke mode (make trace-smoke): only the tracing-overhead row —
    # the <=3% learn-loop overhead gate needs nothing else
    if os.environ.get("BENCH_TRACE_ONLY") == "1":
        for row in _run_row_budgeted(
            "trace_overhead", "pipeline_trace_overhead_frac",
            _measure_trace_overhead, left, share=0.9,
        ):
            emit(row)
        return
    # obsnet mode (make obsnet-smoke / BENCH_OBSNET_ONLY=1): only the
    # telemetry-relay overhead row — the <=3% learn-loop tax gate needs
    # nothing else
    if os.environ.get("BENCH_OBSNET_ONLY") == "1":
        for row in _run_row_budgeted(
            "obs_net_overhead", "obs_net_overhead_frac",
            _measure_obs_net_overhead, left, share=0.9,
        ):
            emit(row)
        return
    # multitask mode (make multitask-smoke / BENCH_MULTITASK_ONLY=1): only
    # the 2-game-vs-1-game learner-throughput row
    if os.environ.get("BENCH_MULTITASK_ONLY") == "1":
        for row in _run_row_budgeted(
            "multitask_throughput", "multitask_learn_steps_per_sec",
            _measure_multitask_throughput, left, share=0.9,
        ):
            emit(row)
        return
    if os.environ.get("BENCH_APEX_ONLY") == "1":
        for row in _run_row_budgeted(
            "weight_publish", "weight_publish_bytes_per_publish",
            _measure_weight_publish, left, share=0.15,
        ):
            emit(row)
        for row in _run_row_budgeted(
            "trace_overhead", "pipeline_trace_overhead_frac",
            _measure_trace_overhead, left, share=0.25,
        ):
            emit(row)
        for row in _run_row_budgeted(
            "apex_loop", "apex_loop_steps_per_sec",
            _measure_apex_loop, left, share=0.4,
        ):
            emit(row)
        for row in _run_row_budgeted(
            "replay_reuse", "replay_reuse_learn_steps_per_sec",
            _measure_replay_reuse, left, share=0.6,
        ):
            emit(row)
        for row in _run_row_budgeted(
            "sample_path", "replay_sample_path_batches_per_sec",
            _measure_sample_path, left, share=0.7,
        ):
            emit(row)
        for row in _run_row_budgeted(
            "replay_net_path", "replay_net_sample_batches_per_sec",
            _measure_replay_net_path, left, share=0.9,
        ):
            emit(row)
        return
    # multitask tax row (report-only via bench_diff: the trajectory records
    # it, machine weather must not gate it): 2-game task-conditioned learn
    # path vs the single-game one at the same toy net size
    for row in _run_row_budgeted(
        "multitask_throughput", "multitask_learn_steps_per_sec",
        _measure_multitask_throughput, left, share=0.15,
    ):
        emit(row)

    cfg = Config()  # reference defaults: 84x84x4, N=N'=64, K=32, batch 32
    num_actions = 18  # SABER full action set
    batch_size = cfg.batch_size

    state = init_train_state(cfg, num_actions, jax.random.PRNGKey(0))
    learn = jax.jit(build_learn_step(cfg, num_actions), donate_argnums=0)

    rng = np.random.default_rng(0)

    def host_batch():
        return Batch(
            obs=rng.integers(0, 255, (batch_size, *cfg.state_shape), dtype=np.uint8),
            action=rng.integers(0, num_actions, batch_size).astype(np.int32),
            reward=rng.normal(size=batch_size).astype(np.float32),
            next_obs=rng.integers(0, 255, (batch_size, *cfg.state_shape), dtype=np.uint8),
            discount=np.full(batch_size, 0.99**3, np.float32),
            weight=np.ones(batch_size, np.float32),
        )

    key = jax.random.PRNGKey(1)

    def step(state, hb, key):
        # the production staging path (flat-byte frame transfers inside)
        batch = to_device_batch(hb)
        key, k = jax.random.split(key)
        state, info = learn(state, batch, k)
        return state, info, key

    state, info, key = step(state, host_batch(), key)  # compile
    jax.block_until_ready(info["loss"])
    print(f"bench: learn compiled t={time.monotonic()-t_start:.1f}s",
          file=sys.stderr, flush=True)
    for _ in range(2):  # warmup
        state, info, key = step(state, host_batch(), key)
    jax.block_until_ready(info["loss"])

    # the JAX_PLATFORMS=cpu run is a smoke, not a stress of the host: a few
    # iterations only.
    # budget checks must observe DEVICE time, not dispatch time (jit calls
    # are async), so sync every chunk before consulting the clock
    # chunk large enough that the per-chunk sync RTT stays negligible next
    # to the chunk's device time (3 syncs over 300 iters)
    max_iters = 300 if platform != "cpu" else 8
    chunk = 100 if platform != "cpu" else 2
    batches = [host_batch() for _ in range(8)]
    # r02/r05 stabilization: the first chunk absorbs allocator/cache warmup
    # and (on a contended box) scheduler noise — per-chunk rates are kept,
    # the first is trimmed, and the row reports the CHUNK-MEDIAN rate with
    # n_iters carried so cross-round comparisons can see the sample size
    chunk_rates = []
    t0 = time.perf_counter()
    t_chunk = t0
    n = 0
    while n < max_iters and (n < 1 or left() > BUDGET_SECS * 0.5):
        for _ in range(chunk):
            state, info, key = step(state, batches[n % 8], key)
            n += 1
        jax.block_until_ready(info["loss"])
        now = time.perf_counter()
        chunk_rates.append(chunk / (now - t_chunk))
        t_chunk = now

    trimmed = chunk_rates[1:] if len(chunk_rates) > 1 else chunk_rates
    steps_per_sec = sorted(trimmed)[len(trimmed) // 2]  # chunk-median
    host_feed_row = {
        "metric": "iqn_learner_steps_per_sec_atari_shape",
        "value": round(steps_per_sec, 2),
        "unit": f"learn_steps/s (batch=32, 84x84x4, N=N'=64, {platform}; "
                "chunk-median, first chunk trimmed)",
        "vs_baseline": round(steps_per_sec / 75.0, 3),
        "path": "host_feed",
        "n_iters": n,
    }

    # ---- device-resident replay mode (the headline when it runs) ---------
    # The learner the framework actually ships for single-chip Ape-X: the
    # PER ring lives in HBM (replay/device.py) and sample -> learn ->
    # priority write-back is one XLA graph, so a learn step involves no
    # host->device batch at all.  Measured with sampling + priority
    # write-back INCLUDED, which is what the reference learner's loop does
    # per step (SURVEY §3.1).  Skipped on CPU (minutes per step).
    if platform == "cpu":
        # host-feed first (crash-safe: each row is kept the moment it is
        # printed), then the pipeline micro rows EACH under their own budget
        # slice (r05 regression: one overrunning row emitted a timeout row's
        # worth of silence and dropped every row behind it), then host-feed
        # AGAIN so the headline (last stdout line) stays the cross-round
        # comparable metric regardless of what the micro phases measured
        emit(host_feed_row)
        if left() > 45:
            for row in _run_row_budgeted(
                "weight_publish", "weight_publish_bytes_per_publish",
                _measure_weight_publish, left, share=0.15,
            ):
                emit(row)
            for row in _run_row_budgeted(
                "trace_overhead", "pipeline_trace_overhead_frac",
                _measure_trace_overhead, left, share=0.3,
            ):
                emit(row)
            for row in _run_row_budgeted(
                "apex_loop", "apex_loop_steps_per_sec",
                _measure_apex_loop, left, share=0.45,
            ):
                emit(row)
            for row in _run_row_budgeted(
                "replay_reuse", "replay_reuse_learn_steps_per_sec",
                _measure_replay_reuse, left, share=0.5,
            ):
                emit(row)
            for row in _run_row_budgeted(
                "sample_path", "replay_sample_path_batches_per_sec",
                _measure_sample_path, left, share=0.6,
            ):
                emit(row)
            for row in _run_row_budgeted(
                "replay_net_path", "replay_net_sample_batches_per_sec",
                _measure_replay_net_path, left, share=0.7,
            ):
                emit(row)
        else:
            print(f"bench: skipping micro phases, {left():.0f}s left",
                  file=sys.stderr, flush=True)
        emit(host_feed_row)
        return
    # host-feed first, so the device-replay row — the learner the framework
    # actually ships — is the last line and with it the headline.  This phase
    # is the point of a device run: one that cannot run it, or that raises in
    # it, fails instead of leaving the host-feed row as the headline.
    emit(host_feed_row)
    if left() < BUDGET_SECS * 0.35:
        raise RuntimeError(
            f"bench: {left():.0f}s of the {BUDGET_SECS:.0f}s budget left, too "
            "little for the device-replay phase (raise BENCH_WATCHDOG_SECS)")
    device_row = _measure_device_replay(cfg, num_actions, left)
    if device_row is None:
        raise RuntimeError("bench: the device-replay phase measured nothing")
    emit(device_row)


def _run_row_budgeted(path_name, metric, fn, left, share) -> list:
    """Per-row time budgets (ISSUE 6 satellite; the r05 regression): each
    bench row gets its OWN slice of the run's remaining soft budget, and a
    row that overruns (or dies) emits a labelled ``"status": "timeout"`` /
    ``"error"`` row instead of silently dropping itself AND every row queued
    behind it.  ``share`` is the fraction of the remaining budget this row
    may spend; the row's ``left`` callable is clamped to both its slice and
    the run's global budget."""
    t0 = time.monotonic()
    budget = max(left() * share, 0.0)

    def row_left() -> float:
        return min(budget - (time.monotonic() - t0), left())

    rows = []
    try:
        rows = fn(row_left) or []
    except Exception as e:  # noqa: BLE001 — a dead row must not kill the run
        print(f"bench: {path_name} row failed: {e!r}", file=sys.stderr)
    if rows:
        return rows
    status = "timeout" if row_left() <= 0 else "error"
    print(f"bench: {path_name} row gave up (status={status}, "
          f"{row_left():.0f}s of its {budget:.0f}s slice left)",
          file=sys.stderr, flush=True)
    return [{
        "metric": metric,
        "value": 0.0,
        "unit": f"{path_name} row produced no measurement",
        "vs_baseline": None,
        "path": path_name,
        "status": status,
    }]


def _measure_weight_publish(left=None) -> list:
    """Weight-distribution bytes bench (ISSUE 8): bytes/publish for a real
    Rainbow-IQN param tree under three distribution schemes — fp32 full
    (the seed's WeightMailbox/rollout payload), bf16 full
    (cfg.bf16_weight_sync), and the int8-delta codec (utils/quantize.py:
    periodic base snapshot + int8 per-tensor deltas, closed-loop).  One row
    carries all three plus ``ratio_vs_fp32``; `make perf-smoke` gates the
    ratio at >= 3x.  Bytes are deterministic (no timing), so the only
    budget risk is the one-time flax init; the drift between publishes is
    simulated as small Gaussian steps (an Adam-scale perturbation), which
    is the delta codec's operating distribution.  The run also asserts the
    decoder's reconstruction stays bit-exact with the encoder — a silently
    divergent codec must fail the bench, not ship."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.utils import quantize as quantize_mod

    # toy-but-real tree: the bytes RATIO is shape-independent (every scheme
    # scales with param count), so the apex_loop toy shape keeps the row
    # cheap on CPU while exercising a genuine multi-layer flax tree
    h = w = int(os.environ.get("BENCH_WP_FRAME", "44"))
    publishes = int(os.environ.get("BENCH_WP_PUBLISHES", "20"))
    base_interval = int(os.environ.get("BENCH_WP_BASE_INTERVAL", "10"))
    cfg = Config().replace(
        compute_dtype="float32", frame_height=h, frame_width=w,
        history_length=2, hidden_size=64, num_cosines=16,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        publish_base_interval=base_interval,
    )
    state = init_train_state(cfg, 6, jax.random.PRNGKey(0))
    params = jax.tree.map(np.asarray, state.params)
    fp32_bytes = quantize_mod.tree_bytes(params)
    if left() < 5:
        return []

    rng = np.random.default_rng(0)
    flat = quantize_mod.flatten_tree(params)
    enc = quantize_mod.DeltaEncoder(base_interval)
    dec = quantize_mod.DeltaDecoder()
    delta_bytes = 0
    for v in range(1, publishes + 1):
        flat = {p: a + rng.normal(scale=1e-4, size=a.shape).astype(np.float32)
                for p, a in flat.items()}
        packet = enc.encode(quantize_mod.unflatten_tree(flat), v)
        delta_bytes += packet.nbytes()
        dec.apply(packet)
    ref = quantize_mod.flatten_tree(enc.reconstructed())
    got = quantize_mod.flatten_tree(dec.params())
    exact = all(np.array_equal(ref[p], got[p]) for p in ref)
    if not exact:
        raise RuntimeError("delta decoder diverged from encoder (not bit-exact)")
    per_publish = delta_bytes / publishes
    return [{
        "metric": "weight_publish_bytes_per_publish",
        "value": round(per_publish, 1),
        "unit": (
            f"bytes/publish (int8-delta codec, base every {base_interval} "
            f"publishes ({'bf16' if quantize_mod.HAVE_ML_DTYPES else 'fp32'} "
            f"base), {publishes} publishes of a {fp32_bytes // 1024}KiB-fp32 "
            "Rainbow-IQN tree, decoder verified bit-exact vs encoder; vs "
            "fp32-full and bf16-full rows alongside)"
        ),
        "vs_baseline": None,  # bytes row — not a learn-steps/s number
        "path": "weight_publish",
        "fp32_bytes_per_publish": fp32_bytes,
        "bf16_bytes_per_publish": fp32_bytes // 2,
        "ratio_vs_fp32": round(fp32_bytes / max(per_publish, 1e-9), 3),
        "ratio_vs_bf16": round((fp32_bytes // 2) / max(per_publish, 1e-9), 3),
        "publishes": publishes,
        "base_interval": base_interval,
    }]


def _measure_trace_overhead(left=None) -> list:
    """Pipeline-tracing overhead row (ISSUE 9): the SAME toy learner loop —
    sharded replay append + prefetch sample + jitted learn + write-back
    ring, tracer attached in BOTH arms (the production wiring) — once with
    span sampling ON (1-in-N span_link rows written to a real file) and
    once at the trace_sample_every=0 DEFAULT.  The arms differ only in the
    sampling knob, so ``overhead_frac`` = 1 - traced/default measures
    exactly what the acceptance bounds: what turning span emission on costs
    over the default loop.  (The always-on lag metrics ride in both arms;
    their cost is covered by the unchanged apex_loop trajectory bench_diff
    gates across rounds, and the default path's numerics by the tier-1
    bitwise tests.)  `make trace-smoke` gates the row at <= 3%."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import tempfile

    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.obs.pipeline_trace import PipelineTracer
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step, init_train_state
    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger
    from rainbow_iqn_apex_tpu.utils.prefetch import make_replay_prefetcher
    from rainbow_iqn_apex_tpu.utils.writeback import WritebackRing

    platform = jax.devices()[0].platform
    h = w = int(os.environ.get("BENCH_TO_FRAME", "44"))
    lanes = int(os.environ.get("BENCH_TO_LANES", "64"))
    ticks = int(os.environ.get("BENCH_TO_TICKS", "4"))
    iters = int(os.environ.get("BENCH_TO_ITERS", "120"))
    # a ratio-of-rates row needs BOTH best-ofs converged: 4 minimum reps
    # (the apex_loop rows use 3) because the gate is a 3% margin, thinner
    # than the sandbox's single-rep scheduler noise
    reps = int(os.environ.get("BENCH_TO_REPS", "4"))
    max_reps = int(os.environ.get("BENCH_TO_MAX_REPS", "8"))
    sample_every = int(os.environ.get("BENCH_TO_SAMPLE_EVERY", "16"))
    num_actions = 6
    cfg = Config().replace(
        compute_dtype="float32", frame_height=h, frame_width=w,
        history_length=2, hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, multi_step=3, prefetch_depth=2,
    )
    # undonated jit on CPU for the same reason as the apex_loop row
    learn = jax.jit(build_learn_step(cfg, num_actions))
    rng = np.random.default_rng(0)
    pool = [
        (
            rng.integers(0, 255, (lanes, h, w), dtype=np.uint8),
            rng.integers(0, num_actions, lanes).astype(np.int64),
            rng.normal(size=lanes).astype(np.float32),
            (rng.random(lanes) < 0.01),
        )
        for _ in range(16)
    ]
    import shutil

    tmpdir = tempfile.mkdtemp(prefix="ria_trace_bench_")

    def run(traced: bool, run_iters: int, tag: int) -> "tuple[float, int]":
        memory = ShardedReplay.build(
            1, 1 << 15, lanes, frame_shape=(h, w), history=2, n_step=3,
            gamma=0.99, priority_exponent=0.5, seed=0,
        )
        logger = MetricsLogger(
            os.path.join(tmpdir, f"trace_{tag}_{int(traced)}.jsonl"),
            "bench", echo=False)
        ptrace = PipelineTracer(
            logger, MetricRegistry(),
            sample_every=sample_every if traced else 0)
        memory.attach_tracer(ptrace)
        ring = WritebackRing(cfg.writeback_depth, tracer=ptrace)

        def actor_tick(t: int) -> None:
            f, a, r, d = pool[t % len(pool)]
            tid = ptrace.maybe_trace("a", memory.append_ticks + 1)
            with ptrace.span("append", tid):
                memory.append_batch(f, a, r, d)

        for t in range(4096 // lanes + 8):
            actor_tick(t)
        state = init_train_state(cfg, num_actions, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        pf = make_replay_prefetcher(memory, cfg, lambda: 0.6)
        try:
            for _ in range(3):  # compile + warm
                idx, batch = pf.get()
                key, k = jax.random.split(key)
                state, info = learn(state, batch, k)
            jax.block_until_ready(info["loss"])
            n = 0
            t0 = time.perf_counter()
            for i in range(run_iters):
                for t in range(ticks):
                    actor_tick(i * ticks + t)
                step = i + 1
                ltid = ptrace.maybe_trace("l", step)
                with ptrace.span("gather", ltid):
                    idx, batch = pf.get()
                links = (ptrace.link_ids("a", memory.trace_ids(idx))
                         if ltid else ())
                key, k = jax.random.split(key)
                with ptrace.span("learn_step", ltid, links=links, step=step):
                    state, info = learn(state, batch, k)
                retired = ring.push(step, idx, info)
                if retired is not None:
                    memory.update_priorities(retired.idx, retired.priorities)
                if step % 50 == 0:
                    ptrace.emit_lag_row(step)
                n = step
                if left() < 15:
                    break
            for retired in ring.drain():
                memory.update_priorities(retired.idx, retired.priorities)
            jax.block_until_ready(info["loss"])
            return n / (time.perf_counter() - t0), n
        finally:
            pf.close()
            logger.close()

    best_u = best_t = 0.0
    rep = 0
    try:
        while rep < max_reps and left() > 25:
            prev = (best_u, best_t)
            order = (False, True) if rep % 2 == 0 else (True, False)
            for traced in order:
                sps, _ = run(traced, iters, rep)
                if traced:
                    best_t = max(best_t, sps)
                else:
                    best_u = max(best_u, sps)
                if left() < 20:
                    break
            rep += 1
            if rep >= reps and best_u and best_t:
                if best_u <= prev[0] * 1.02 and best_t <= prev[1] * 1.02:
                    break
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    if not (best_u and best_t):
        return []
    overhead = max(1.0 - best_t / best_u, 0.0)
    return [{
        "metric": "pipeline_trace_overhead_frac",
        "value": round(overhead, 4),
        "unit": (
            f"fraction of learn-loop throughput lost to span sampling "
            f"(toy {h}x{w}x2 batch={cfg.batch_size} loop on {platform}, "
            f"tracer attached in both arms, 1-in-{sample_every} span_link "
            f"JSONL emission vs the trace_sample_every=0 default; "
            f"best-of-{rep} interleaved reps x {iters} iters)"
        ),
        "vs_baseline": None,
        "path": "trace_overhead",
        "traced_steps_per_sec": round(best_t, 2),
        "untraced_steps_per_sec": round(best_u, 2),
        "sample_every": sample_every,
        "reps": rep,
    }]


def _measure_obs_net_overhead(left=None) -> list:
    """Live-telemetry-plane overhead row (ISSUE 18): the SAME toy learner
    loop as the trace_overhead row — sharded replay append + prefetch
    sample + jitted learn + write-back ring, a MetricsLogger emitting one
    `learn` row per step in BOTH arms — once with an ObsRelay attached and
    STREAMING to a live loopback ObsCollector (the production obs_net
    wiring: observer fan-out, spool, framed-socket sends, periodic registry
    snapshots, collector ingest on the same box) and once at the obs_net
    default (no relay constructed).  The arms differ only in the relay, so
    ``overhead_frac`` = 1 - on/off is exactly what the acceptance bounds:
    what turning the live fleet view on costs the learn loop.  `make
    obsnet-smoke` gates the row at <= 3%."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import shutil
    import tempfile

    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.obs.net.collector import ObsCollector
    from rainbow_iqn_apex_tpu.obs.net.relay import ObsRelay
    from rainbow_iqn_apex_tpu.obs.registry import MetricRegistry
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step, init_train_state
    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
    from rainbow_iqn_apex_tpu.utils.logging import MetricsLogger
    from rainbow_iqn_apex_tpu.utils.prefetch import make_replay_prefetcher
    from rainbow_iqn_apex_tpu.utils.writeback import WritebackRing

    platform = jax.devices()[0].platform
    h = w = int(os.environ.get("BENCH_ON_FRAME", "44"))
    lanes = int(os.environ.get("BENCH_ON_LANES", "64"))
    ticks = int(os.environ.get("BENCH_ON_TICKS", "4"))
    iters = int(os.environ.get("BENCH_ON_ITERS", "120"))
    # same convergence discipline as trace_overhead: a 3% gate is thinner
    # than single-rep scheduler noise, so interleave best-ofs
    reps = int(os.environ.get("BENCH_ON_REPS", "4"))
    max_reps = int(os.environ.get("BENCH_ON_MAX_REPS", "8"))
    num_actions = 6
    cfg = Config().replace(
        compute_dtype="float32", frame_height=h, frame_width=w,
        history_length=2, hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, multi_step=3, prefetch_depth=2,
    )
    learn = jax.jit(build_learn_step(cfg, num_actions))
    rng = np.random.default_rng(0)
    pool = [
        (
            rng.integers(0, 255, (lanes, h, w), dtype=np.uint8),
            rng.integers(0, num_actions, lanes).astype(np.int64),
            rng.normal(size=lanes).astype(np.float32),
            (rng.random(lanes) < 0.01),
        )
        for _ in range(16)
    ]
    tmpdir = tempfile.mkdtemp(prefix="ria_obsnet_bench_")

    def run(relayed: bool, run_iters: int, tag: int) -> float:
        memory = ShardedReplay.build(
            1, 1 << 15, lanes, frame_shape=(h, w), history=2, n_step=3,
            gamma=0.99, priority_exponent=0.5, seed=0,
        )
        logger = MetricsLogger(
            os.path.join(tmpdir, f"obsnet_{tag}_{int(relayed)}.jsonl"),
            "bench", echo=False)
        collector = relay = None
        if relayed:
            collector = ObsCollector(
                host="127.0.0.1", port=0, tick_s=0.5, serve_http=False,
                rules=[])
            relay = ObsRelay(
                collector_addr=("127.0.0.1", collector.port),
                role="learner", run_id="bench",
                registry=MetricRegistry(), logger=logger, snapshot_s=0.5)
            logger.add_observer(relay.observe)

        def actor_tick(t: int) -> None:
            f, a, r, d = pool[t % len(pool)]
            memory.append_batch(f, a, r, d)

        for t in range(4096 // lanes + 8):
            actor_tick(t)
        state = init_train_state(cfg, num_actions, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        pf = make_replay_prefetcher(memory, cfg, lambda: 0.6)
        ring = WritebackRing(cfg.writeback_depth)
        try:
            for _ in range(3):  # compile + warm
                idx, batch = pf.get()
                key, k = jax.random.split(key)
                state, info = learn(state, batch, k)
            jax.block_until_ready(info["loss"])
            n = 0
            t0 = time.perf_counter()
            for i in range(run_iters):
                for t in range(ticks):
                    actor_tick(i * ticks + t)
                step = i + 1
                idx, batch = pf.get()
                key, k = jax.random.split(key)
                state, info = learn(state, batch, k)
                retired = ring.push(step, idx, info)
                if retired is not None:
                    memory.update_priorities(retired.idx, retired.priorities)
                logger.log("learn", step=step, frames=step * lanes * ticks,
                           loss=0.5)
                n = step
                if left() < 15:
                    break
            for retired in ring.drain():
                memory.update_priorities(retired.idx, retired.priorities)
            jax.block_until_ready(info["loss"])
            return n / (time.perf_counter() - t0)
        finally:
            pf.close()
            if relay is not None:
                relay.close(flush_timeout_s=1.0)
            if collector is not None:
                collector.stop()
            logger.close()

    best_off = best_on = 0.0
    rep = 0
    try:
        while rep < max_reps and left() > 25:
            prev = (best_off, best_on)
            order = (False, True) if rep % 2 == 0 else (True, False)
            for relayed in order:
                sps = run(relayed, iters, rep)
                if relayed:
                    best_on = max(best_on, sps)
                else:
                    best_off = max(best_off, sps)
                if left() < 20:
                    break
            rep += 1
            if rep >= reps and best_off and best_on:
                if best_off <= prev[0] * 1.02 and best_on <= prev[1] * 1.02:
                    break
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    if not (best_off and best_on):
        return []
    overhead = max(1.0 - best_on / best_off, 0.0)
    return [{
        "metric": "obs_net_overhead_frac",
        "value": round(overhead, 4),
        "unit": (
            f"fraction of learn-loop throughput lost to the obs_net relay "
            f"(toy {h}x{w}x2 batch={cfg.batch_size} loop on {platform}, one "
            f"learn row logged per step, relay streaming to a live loopback "
            f"collector vs the obs_net=False default; "
            f"best-of-{rep} interleaved reps x {iters} iters)"
        ),
        "vs_baseline": None,
        "path": "obs_net_overhead",
        "on_steps_per_sec": round(best_on, 2),
        "off_steps_per_sec": round(best_off, 2),
        "reps": rep,
    }]


def _measure_chaos_overhead(left=None) -> list:
    """chaos_overhead: what the net-chaos seam costs when DISARMED
    (ISSUE 19).  Every plane routes freshly-created sockets through
    ``chaos.maybe_wrap`` unconditionally; the off-path guarantee is that
    with no spec armed the seam returns the socket UNCHANGED, so the tax
    is one function call per connection — not per byte.  Two arms over
    the same framed-socket echo loop (send_frame -> peer echo ->
    recv_frame, 4 KiB blobs): one with the production seam in place
    (disarmed ``chaos.install(None)`` + ``maybe_wrap`` on both ends) and
    one bypassing the seam entirely.  ``overhead_frac`` = 1 - on/off;
    `make netchaos-smoke` gates it at <= 1%.  A 1% gate is far thinner
    than loopback round-trip noise: throughput drifts 20-30% across
    minutes (CPU frequency, sibling load) and even BACK-TO-BACK whole-arm
    runs disagree by +-4-6%, so best-of-maxima and coarse paired ratios
    both flake the gate.  Instead both arms are set up concurrently (the
    idle arm's echo thread is parked in a blocking recv, costing nothing)
    and each rep alternates small BLOCKS of round trips between them,
    accumulating per-arm time — noise slower than a block (~10 ms)
    cancels inside every rep.  The row reports 1 - median(per-rep
    ratios).  Even so, per-PROCESS placement luck (which cores the echo
    threads land on) can hold a 2% phantom difference between bitwise-
    identical arms for a whole run, so the row ALSO reports
    ``seam_identity``: whether the disarmed seam returned the socket
    object unchanged — the structural guarantee that the per-byte cost
    is exactly zero.  The smoke gate accepts a verified identity OR a
    measured tax <= 1%; a regression that makes the disarmed seam
    non-identity loses the short-circuit and faces the measured gate."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import socket
    import threading

    from rainbow_iqn_apex_tpu.netcore import chaos
    from rainbow_iqn_apex_tpu.netcore.framing import recv_frame, send_frame

    iters = int(os.environ.get("BENCH_CHAOS_ITERS", "3000"))
    reps = int(os.environ.get("BENCH_CHAOS_REPS", "4"))
    max_reps = int(os.environ.get("BENCH_CHAOS_MAX_REPS", "8"))
    block = 128  # round trips per interleave slice, ~10 ms
    blob = b"\x5a" * 4096

    class Arm:
        def __init__(self, seamed: bool) -> None:
            a, b = socket.socketpair()
            a.settimeout(30.0)
            b.settimeout(30.0)
            if seamed:
                chaos.install(None)  # the default: nothing armed
                a = chaos.maybe_wrap(a, peer="bench-client")
                b = chaos.maybe_wrap(b, peer="bench-server")
            self.a, self.b = a, b
            self.elapsed = 0.0
            self.n = 0

            def echo() -> None:
                try:
                    while True:
                        got = recv_frame(b, max_frame_bytes=1 << 20)
                        if got is None or got[0].get("op") == "stop":
                            return
                        send_frame(b, got[0], got[1])
                except OSError:  # bench teardown, not a measurement
                    return

            self.t = threading.Thread(target=echo, daemon=True)
            self.t.start()

        def run_block(self, count: int) -> None:
            t0 = time.perf_counter()
            for i in range(count):
                send_frame(self.a, {"op": "echo", "i": i}, blob)
                recv_frame(self.a, max_frame_bytes=1 << 20)
            self.elapsed += time.perf_counter() - t0
            self.n += count

        def close(self) -> None:
            try:
                send_frame(self.a, {"op": "stop"})
                self.t.join(timeout=5.0)
            except OSError:
                pass
            self.a.close()
            self.b.close()

    def run_pair(flip: bool):
        """One rep: both arms live, alternating blocks (the arm that goes
        first swaps every block), per-arm time accumulated.  ``flip``
        swaps which arm is CONSTRUCTED first — thread/core placement is
        sticky within a rep, so construction order must alternate across
        reps too.  Returns (on_rtps, off_rtps) for this rep."""
        arms = {}
        for seamed in ((True, False) if flip else (False, True)):
            arms[seamed] = Arm(seamed)
        try:
            for arm in arms.values():
                arm.run_block(64)  # warm the path (allocator, frame codec)
                arm.elapsed, arm.n = 0.0, 0
            blocks = max(iters // block, 1)
            for i in range(blocks):
                order = (False, True) if (i + flip) % 2 == 0 else (True, False)
                for seamed in order:
                    arms[seamed].run_block(block)
                if left() < 15:
                    break
            on, off = arms[True], arms[False]
            if not (on.elapsed and off.elapsed):
                return None
            return (on.n / on.elapsed, off.n / off.elapsed)
        finally:
            for arm in arms.values():
                arm.close()

    def median(xs: list) -> float:
        xs = sorted(xs)
        mid = len(xs) // 2
        return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2.0

    ratios: list = []
    best_on = best_off = 0.0
    rep = 0
    while rep < max_reps and left() > 20:
        prev_med = median(ratios) if ratios else None
        pair = run_pair(flip=bool(rep % 2))
        if pair is None:
            break
        on_rtps, off_rtps = pair
        best_on = max(best_on, on_rtps)
        best_off = max(best_off, off_rtps)
        ratios.append(on_rtps / off_rtps)
        rep += 1
        if rep >= reps and prev_med is not None:
            # the median moved < 0.2pp on the last rep: converged
            if abs(median(ratios) - prev_med) <= 0.002:
                break
    if not ratios:
        return []
    overhead = max(1.0 - median(ratios), 0.0)
    sa, sb = socket.socketpair()
    try:
        chaos.install(None)
        seam_identity = chaos.maybe_wrap(sa, peer="bench-probe") is sa
    finally:
        sa.close()
        sb.close()
    return [{
        "metric": "net_chaos_overhead_frac",
        "value": round(overhead, 4),
        "unit": (
            f"fraction of framed-socket echo throughput lost to the "
            f"DISARMED chaos.maybe_wrap seam (4 KiB blobs over a loopback "
            f"socketpair, seam-in-place vs seam-bypassed; median of {rep} "
            f"block-interleaved paired reps x {iters} round trips)"
        ),
        "vs_baseline": None,
        "path": "chaos_overhead",
        "on_rtps": round(best_on, 1),
        "off_rtps": round(best_off, 1),
        "seam_identity": seam_identity,
        "reps": rep,
    }]


def _measure_multitask_throughput(left=None) -> list:
    """multitask_throughput: the multi-game tax on the learn path.

    Two arms at the SAME toy net size over the REAL sample->to_device->
    learn-step path: (a) single-game — ShardedReplay + ops.learn; (b)
    2-game — MultiGameReplay's interleaved sample + the task-conditioned
    MultiGameIQN learn step (game-embedding torso, masked double-Q).  The
    ratio records what running N games in one pod costs per learn step
    (game embedding add + mask where + interleave bookkeeping — expected a
    few percent).  Report-only in bench_diff: raw rates swing with machine
    weather; the ratio is the trajectory record (docs/MULTITASK.md).
    """
    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.multitask.ops import (
        build_mt_learn_step,
        init_mt_train_state,
    )
    from rainbow_iqn_apex_tpu.multitask.replay import MultiGameReplay
    from rainbow_iqn_apex_tpu.multitask.spec import MultiGameSpec
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step, init_train_state
    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay

    iters = int(os.environ.get("BENCH_MT_ITERS", "40"))
    reps = int(os.environ.get("BENCH_MT_REPS", "2"))
    lanes = int(os.environ.get("BENCH_MT_LANES", "8"))
    prefill = int(os.environ.get("BENCH_MT_PREFILL", "192"))
    spec = MultiGameSpec.probe(("toy:catch", "toy:chain"))
    cfg = Config(
        compute_dtype="float32", history_length=2, hidden_size=64,
        num_cosines=16, num_tau_samples=8, num_tau_prime_samples=8,
        num_quantile_samples=4, batch_size=32, multi_step=3, gamma=0.9,
        use_native_sumtree=True,
    )
    rng = np.random.default_rng(0)
    h, w = spec.frame_shape

    def prefill_mem(mem):
        for _ in range(prefill):
            mem.append_batch(
                rng.integers(0, 255, (lanes, h, w), np.uint8),
                rng.integers(0, 2, lanes).astype(np.int32),
                rng.normal(size=lanes).astype(np.float32),
                rng.random(lanes) < 0.05,
                np.abs(rng.normal(size=lanes)) + 0.1,
            )
        return mem

    common = dict(history=cfg.history_length, n_step=cfg.multi_step,
                  gamma=cfg.gamma, seed=3)
    mem_single = prefill_mem(ShardedReplay.build(
        2, 4096, lanes, frame_shape=spec.frame_shape, **common))
    mem_mt = prefill_mem(MultiGameReplay.build_games(
        spec, 1, 4096, lanes, schedule="uniform", **common))

    state_single = init_train_state(
        cfg, spec.max_actions, jax.random.PRNGKey(0),
        state_shape=(h, w, cfg.history_length))
    state_mt = init_mt_train_state(cfg, spec, jax.random.PRNGKey(0))
    learn_single = jax.jit(
        build_learn_step(cfg, spec.max_actions), donate_argnums=0)
    learn_mt = jax.jit(build_mt_learn_step(cfg, spec), donate_argnums=0)
    key = jax.random.PRNGKey(1)

    def run(learn, state, mem, n: int) -> "tuple[float, Any]":
        nonlocal key
        info = None
        t0 = time.monotonic()
        for _ in range(n):
            batch = to_device_batch(mem.sample(cfg.batch_size, 0.5))
            key, k = jax.random.split(key)
            state, info = learn(state, batch, k)
        jax.block_until_ready(info["loss"])
        return (time.monotonic() - t0, state)

    # one warmup step per arm (compile), then alternating best-of reps so
    # scheduler weather hits both arms evenly
    _dt, state_single = run(learn_single, state_single, mem_single, 1)
    _dt, state_mt = run(learn_mt, state_mt, mem_mt, 1)
    best = {"single": float("inf"), "mt": float("inf")}
    for _rep in range(reps):
        if left is not None and left() <= 0:
            break
        dt, state_single = run(learn_single, state_single, mem_single, iters)
        best["single"] = min(best["single"], dt)
        dt, state_mt = run(learn_mt, state_mt, mem_mt, iters)
        best["mt"] = min(best["mt"], dt)
    if not all(np.isfinite(v) for v in best.values()):
        return []
    single_sps = iters / max(best["single"], 1e-9)
    mt_sps = iters / max(best["mt"], 1e-9)
    return [{
        "metric": "multitask_learn_steps_per_sec",
        "value": round(mt_sps, 3),
        "unit": ("learn steps/s, 2-game task-conditioned (interleaved "
                 "sample + MultiGameIQN) vs single-game at the same size"),
        "vs_baseline": None,
        "path": "multitask_throughput",
        "games": spec.num_games,
        "schedule": "uniform",
        "batch_size": cfg.batch_size,
        "single_steps_per_sec": round(single_sps, 3),
        "ratio_vs_single": round(mt_sps / max(single_sps, 1e-9), 4),
    }]


def _measure_replay_reuse(left=None) -> list:
    """replay_reuse row (ISSUE 12 tentpole gate): replay-ratio K=4 vs K=1
    over the REAL sample -> to_device -> fused-learn -> ring-write-back
    loop, in the regime the knob exists for — an ACTOR-BOUND pipeline,
    emulated as a fixed per-sample scarcity stall (``BENCH_RR_SAMPLE_US``,
    the sample-supply analogue of apex_loop's emulated env IPC): the replay
    can only hand the learner one fresh batch every so often, exactly the
    PR-9 `actor-bound` critical_path verdict.  K=4 takes four clipped SGD
    passes per batch inside ONE fori_loop'd executable (ops/learn.py), so
    learn_steps/s should approach 4x the K=1 loop minus the per-pass
    compute that no longer hides under the stall; `make perf-smoke` gates
    ``speedup_vs_k1`` >= 2 at this toy size and bench_diff regresses it
    across rounds.

    The same row carries the MATCHED-ENV-FRAMES eval-parity check: two real
    ``train()`` runs on toy:chain at identical seeds/frames, K=1 vs K=4 —
    ``eval_parity`` requires both final evals finite, zero NaN-guard
    rollbacks under reuse (the IMPACT clip's job), and the K=4 score within
    1.0 of K=1 on the toy's [-1, 1]-ish scale (reuse must not trade speed
    for a destabilized policy)."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import shutil
    import tempfile

    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step, init_train_state
    from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay
    from rainbow_iqn_apex_tpu.utils.writeback import WritebackRing

    platform = jax.devices()[0].platform
    h = w = int(os.environ.get("BENCH_RR_FRAME", "44"))
    lanes = int(os.environ.get("BENCH_RR_LANES", "64"))
    iters_k1 = int(os.environ.get("BENCH_RR_ITERS", "60"))
    reps = int(os.environ.get("BENCH_RR_REPS", "2"))
    max_reps = int(os.environ.get("BENCH_RR_MAX_REPS", "4"))
    reuse_k = int(os.environ.get("BENCH_RR_K", "4"))
    # per-sample scarcity stall: the actor fleet can only refill the replay
    # so fast, so a fresh batch is only WORTH drawing this often — sized so
    # the K=1 loop is clearly sample-bound at the toy step time (the
    # operating point where the PR-9 analyzer says `actor-bound`)
    sample_us = int(os.environ.get("BENCH_RR_SAMPLE_US", "60000"))
    parity_frames = int(os.environ.get("BENCH_RR_PARITY_FRAMES", "320"))
    num_actions = 6
    cfg = Config().replace(
        compute_dtype="float32", frame_height=h, frame_width=w,
        history_length=2, hidden_size=32, num_cosines=8,
        num_tau_samples=4, num_tau_prime_samples=4, num_quantile_samples=4,
        batch_size=16, multi_step=3,
    )

    rng = np.random.default_rng(0)
    memory = PrioritizedReplay(
        1 << 14, (h, w), history=2, n_step=3, gamma=0.99, lanes=lanes,
        priority_exponent=0.5, seed=0,
    )
    for t in range(4096 // lanes + 8):
        memory.append_batch(
            rng.integers(0, 255, (lanes, h, w), dtype=np.uint8),
            rng.integers(0, num_actions, lanes).astype(np.int64),
            rng.normal(size=lanes).astype(np.float32),
            (rng.random(lanes) < 0.01),
        )

    # undonated jit on CPU (donated dispatch runs synchronously there —
    # same note as the apex_loop row)
    learns = {
        k: jax.jit(build_learn_step(
            cfg.replace(replay_ratio=k), num_actions))
        for k in (1, reuse_k)
    }

    def run(k: int, n_samples: int) -> "tuple[float, int]":
        learn = learns[k]
        state = init_train_state(cfg, num_actions, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        ring = WritebackRing(cfg.writeback_depth)
        for _ in range(2):  # compile + warm
            batch = to_device_batch(memory.sample(cfg.batch_size, 0.6))
            key, kk = jax.random.split(key)
            state, info = learn(state, batch, kk)
        jax.block_until_ready(info["loss"])
        n = 0
        t0 = time.perf_counter()
        for i in range(n_samples):
            if sample_us:  # the emulated actor-bound sample supply
                time.sleep(sample_us / 1e6)
            sample = memory.sample(cfg.batch_size, 0.6)
            batch = to_device_batch(sample)
            key, kk = jax.random.split(key)
            state, info = learn(state, batch, kk)
            retired = ring.push((i + 1) * k, sample.idx, info)
            if retired is not None:
                memory.update_priorities(retired.idx, retired.priorities)
            n = i + 1
            if left() < 20:
                break
        for retired in ring.drain():
            memory.update_priorities(retired.idx, retired.priorities)
        jax.block_until_ready(info["loss"])
        return n * k / (time.perf_counter() - t0), n

    best = {1: 0.0, reuse_k: 0.0}
    rep = 0
    while rep < max_reps and left() > 30:
        prev = dict(best)
        order = (1, reuse_k) if rep % 2 == 0 else (reuse_k, 1)
        for k in order:
            # matched WALL budgets, not matched samples: the K arm takes
            # ~K-fold fewer samples through the same stall per learn step
            sps, _ = run(k, iters_k1 if k == 1 else max(iters_k1 // 2, 8))
            best[k] = max(best[k], sps)
            if left() < 25:
                break
        rep += 1
        if rep >= reps and all(best.values()):
            if all(best[k] <= prev[k] * 1.02 for k in best):
                break
    if not all(best.values()):
        return []

    # matched-env-frames eval parity: two REAL toy train() runs, K=1 vs K
    eval_k1 = eval_kn = float("nan")
    rollbacks = -1
    parity = None  # None = parity arm never completed (vs False = failed)
    if left() > 30:
        from rainbow_iqn_apex_tpu.train import train

        tmpdir = tempfile.mkdtemp(prefix="ria_reuse_bench_")
        try:
            scores = {}
            for k in (1, reuse_k):
                tcfg = Config(
                    env_id="toy:chain", compute_dtype="float32",
                    history_length=2, hidden_size=32, num_cosines=8,
                    num_tau_samples=4, num_tau_prime_samples=4,
                    num_quantile_samples=4, batch_size=16,
                    learning_rate=1e-3, multi_step=3, gamma=0.9,
                    memory_capacity=2048, learn_start=64,
                    frames_per_learn=4, replay_ratio=k,
                    target_update_period=64, num_envs_per_actor=4,
                    metrics_interval=50, eval_interval=0,
                    checkpoint_interval=0, eval_episodes=4,
                    stall_timeout_s=0.0, seed=11,
                    results_dir=os.path.join(tmpdir, f"r{k}"),
                    checkpoint_dir=os.path.join(tmpdir, f"c{k}"),
                )
                summary = train(tcfg, max_frames=parity_frames)
                scores[k] = summary
                if left() < 10:
                    break
            if len(scores) == 2:
                eval_k1 = float(scores[1]["eval_score_mean"])
                eval_kn = float(scores[reuse_k]["eval_score_mean"])
                rollbacks = int(scores[reuse_k]["rollbacks"])
                parity = bool(
                    np.isfinite(eval_k1) and np.isfinite(eval_kn)
                    and rollbacks == 0 and eval_kn >= eval_k1 - 1.0
                )
        except Exception as e:  # noqa: BLE001 — parity is part of the row
            print(f"bench: replay_reuse parity arm failed: {e!r}",
                  file=sys.stderr)
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
    else:
        print("bench: replay_reuse budget exhausted before parity arm",
              file=sys.stderr, flush=True)

    return [{
        "metric": "replay_reuse_learn_steps_per_sec",
        "value": round(best[reuse_k], 2),
        "unit": (
            f"learn_steps/s (replay_ratio={reuse_k} fused clipped reuse vs "
            f"K=1 over the real sample->learn->write-back loop on "
            f"{platform}: toy {h}x{w}x2 batch={cfg.batch_size}, "
            f"{sample_us}us emulated actor-bound sample scarcity/sample; "
            f"best-of-{rep} interleaved reps; plus matched-env-frames "
            f"({parity_frames}) toy:chain eval parity K=1 vs K={reuse_k})"
        ),
        "vs_baseline": None,  # toy shape — not comparable to the 75/s class
        "path": "replay_reuse",
        "k": reuse_k,
        "k1_steps_per_sec": round(best[1], 2),
        "speedup_vs_k1": round(best[reuse_k] / max(best[1], 1e-9), 3),
        "eval_k1": None if not np.isfinite(eval_k1) else round(eval_k1, 3),
        "eval_k": None if not np.isfinite(eval_kn) else round(eval_kn, 3),
        "reuse_rollbacks": rollbacks,
        "eval_parity": parity,
        "parity_frames": parity_frames,
        "reps": rep,
    }]


def _measure_sample_path(left=None) -> list:
    """Sample-path micro bench (ISSUE 6): host sum-tree sample+assemble vs
    device-frontier sample+gather at the Atari frame shape, one row with
    both rates and ``speedup_vs_host`` — the >=1.5x gate in `make
    perf-smoke` rides on this row.

    Why the frontier side wins even on the CPU backend: the draw (cumsum +
    searchsorted + IS weights over the mirrored priority vector,
    ``draw_block`` stratified batches per fused dispatch) executes on the
    XLA device queue and overlaps the host gather of the PREVIOUS block, so
    the steady-state per-batch host cost is just the index-driven frame
    gather; the host path pays tree descent + multinomial shard split +
    per-shard assembly + concatenation + IS-weight math serially on the
    sampling thread.  Same interleaved best-of-reps discipline as the
    apex_loop row (the shared sandbox is contended; the fastest repetition
    is the least-contended measurement of each mode)."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import collections

    import numpy as np

    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
    from rainbow_iqn_apex_tpu.replay.frontier import DeviceSampleFrontier

    shards = int(os.environ.get("BENCH_SP_SHARDS", "4"))
    cap = int(os.environ.get("BENCH_SP_CAP", str(1 << 14)))
    lanes = int(os.environ.get("BENCH_SP_LANES", "16"))
    iters = int(os.environ.get("BENCH_SP_ITERS", "200"))
    reps = int(os.environ.get("BENCH_SP_REPS", "3"))
    max_reps = int(os.environ.get("BENCH_SP_MAX_REPS", "6"))
    block = int(os.environ.get("BENCH_SP_BLOCK", "16"))
    B, beta = 32, 0.4

    memory = ShardedReplay.build(
        shards, cap, lanes, frame_shape=(84, 84), history=4, n_step=3, seed=0,
    )
    rng = np.random.default_rng(0)
    pool = [rng.integers(0, 255, (lanes, 84, 84), dtype=np.uint8)
            for _ in range(8)]
    for t in range(cap // lanes):
        if left() < 30:
            print("bench: sample_path budget exhausted during fill",
                  file=sys.stderr, flush=True)
            return []
        memory.append_batch(
            pool[t % 8],
            rng.integers(0, 18, lanes),
            rng.normal(size=lanes).astype(np.float32),
            rng.random(lanes) < 0.01,
            priorities=rng.random(lanes) + 0.05,
        )
    frontier = DeviceSampleFrontier.from_sharded(
        memory, seed=0, draw_block=block
    )

    def run_host(n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            memory.sample(B, beta)
        return n / (time.perf_counter() - t0)

    def run_frontier(n: int) -> float:
        inflight: collections.deque = collections.deque()
        pending: collections.deque = collections.deque()

        def push():
            inflight.append(frontier.draw(B, beta, len(memory)))

        for _ in range(2):
            push()
        done = 0
        t0 = time.perf_counter()
        while done < n:
            if not pending:
                blk = inflight.popleft()
                push()
                idx = np.asarray(blk.idx)
                w = np.asarray(blk.weight)
                for g in range(blk.groups):
                    pending.append((idx[g], w[g]))
            i_b, w_b = pending.popleft()
            memory.assemble_global(i_b, w_b)
            done += 1
        return done / (time.perf_counter() - t0)

    run_frontier(block)  # compile the draw kernel
    run_host(4)  # touch the host path caches
    if left() < 25:
        print("bench: sample_path budget exhausted after warmup",
              file=sys.stderr, flush=True)
        return []

    best_h = best_f = 0.0
    rep = 0
    while rep < max_reps and left() > 15:
        prev = (best_h, best_f)
        order = ("host", "frontier") if rep % 2 == 0 else ("frontier", "host")
        for mode in order:
            if mode == "host":
                best_h = max(best_h, run_host(iters))
            else:
                best_f = max(best_f, run_frontier(iters))
            if left() < 12:
                break
        rep += 1
        if rep >= reps and best_h and best_f:
            if best_h <= prev[0] * 1.02 and best_f <= prev[1] * 1.02:
                break  # neither best-of still improving: converged
    if not (best_h and best_f):
        return []
    return [{
        "metric": "replay_sample_path_batches_per_sec",
        "value": round(best_f, 2),
        "unit": (
            f"sample+assemble batches/s (batch={B}, 84x84x4 Atari shape, "
            f"{shards}-shard replay, {cap} slots; device-frontier "
            f"draw_block={block} + index-driven gather vs host sum-tree "
            f"sample path; best-of-{rep} interleaved reps x {iters} iters)"
        ),
        "vs_baseline": None,  # micro-path — not a learn-steps/s number
        "path": "sample_path",
        "host_batches_per_sec": round(best_h, 2),
        "speedup_vs_host": round(best_f / max(best_h, 1e-9), 3),
        "n_iters": iters,
        "reps": rep,
    }]


def _measure_replay_net_path(left=None) -> list:
    """Cross-host replay sample-path micro bench (ISSUE 16): pipelined
    `SampleClient` batches over a REAL loopback socket against a
    `ReplayShardServer` vs the in-process host sum-tree path over the SAME
    shard block, one row with both rates and ``ratio_vs_host``.

    GATED since ISSUE 20 at an ABSOLUTE floor (bench_diff FLOORS:
    ratio_vs_host >= 0.5).  On one host the dial lands on the AF_UNIX +
    shared-memory arena fast path (replay/net/shm.py), which removes both
    socket kernel copies and the blob checksum — with the server-side
    sample-ahead ring overlapping assembly against the client's decode,
    the wire path typically comes out ABOVE 1.0x the synchronous
    in-process sample loop; 0.5 keeps weather margin while still
    catching a silent fall back to the TCP byte path (~0.2-0.3x)."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import numpy as np

    from rainbow_iqn_apex_tpu.parallel.sharded_replay import ShardedReplay
    from rainbow_iqn_apex_tpu.replay.net.client import (
        ReplayPeer,
        SampleClient,
    )
    from rainbow_iqn_apex_tpu.replay.net.server import ReplayShardServer

    shards = int(os.environ.get("BENCH_RN_SHARDS", "2"))
    cap = int(os.environ.get("BENCH_RN_CAP", str(1 << 12)))
    lanes = int(os.environ.get("BENCH_RN_LANES", "8"))
    iters = int(os.environ.get("BENCH_RN_ITERS", "150"))
    B, beta = 32, 0.4

    memory = ShardedReplay.build(
        shards, cap, lanes, frame_shape=(84, 84), history=4, n_step=3,
        seed=0,
    )
    rng = np.random.default_rng(0)
    pool = [rng.integers(0, 255, (lanes, 84, 84), dtype=np.uint8)
            for _ in range(8)]
    for t in range(cap // lanes):
        if left() < 30:
            print("bench: replay_net_path budget exhausted during "
                  "fill", file=sys.stderr, flush=True)
            return []
        memory.append_batch(
            pool[t % 8],
            rng.integers(0, 18, lanes),
            rng.normal(size=lanes).astype(np.float32),
            rng.random(lanes) < 0.01,
            priorities=rng.random(lanes) + 0.05,
        )

    def run_host(n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            memory.sample(B, beta)
        return n / (time.perf_counter() - t0)

    srv = ReplayShardServer(memory, shard_base=0, host="127.0.0.1",
                            port=0).start()
    peer = ReplayPeer("127.0.0.1", srv.port, peer_id=0)
    sc = SampleClient({0: peer}, B, lambda: beta, depth=3, seed=0)
    try:
        for _ in range(4):  # warm the pipeline + both socket directions
            sc.get(timeout=30)
        run_host(4)  # touch the host path caches
        if left() < 20:
            print("bench: replay_net_path budget exhausted after "
                  "warmup", file=sys.stderr, flush=True)
            return []
        host_rate = run_host(iters)
        t0 = time.perf_counter()
        for _ in range(iters):
            sc.get(timeout=30)
        wire_rate = iters / (time.perf_counter() - t0)
        shm_used = peer.arena is not None  # before close() drops it
    finally:
        sc.close()
        srv.stop()
    return [{
        "metric": "replay_net_sample_batches_per_sec",
        "value": round(wire_rate, 2),
        "unit": (
            f"wire sample batches/s (batch={B}, 84x84x4 Atari shape, "
            f"{shards}-shard block behind one loopback ReplayShardServer, "
            f"{cap} slots; pipelined SampleClient depth=3 vs the same "
            f"memory's in-process sum-tree sample path; {iters} iters)"
        ),
        "vs_baseline": None,  # micro-path — not a learn-steps/s number
        "path": "replay_net_path",
        "host_batches_per_sec": round(host_rate, 2),
        "ratio_vs_host": round(wire_rate / max(host_rate, 1e-9), 3),
        # which transport actually carried the batches: True = the
        # same-host shared-memory arena (replay/net/shm.py) was negotiated;
        # False = plain TCP (the ratio floor in bench_diff will likely trip)
        "shm": shm_used,
        "n_iters": iters,
    }]


def _measure_apex_loop(left=None) -> list:
    """Pipelined-learner-loop bench (ISSUE 5 tentpole): the REAL write-back
    path — PrioritizedReplay sample via the prefetch thread, jitted learn
    step, WritebackRing priority write-back — around a toy-shape workload,
    measured at writeback_depth=0 (the seed's one-blocking-sync-per-step
    loop) vs the configured depth.  One row is emitted carrying BOTH rates
    plus their ratio, so a single line proves (or disproves) that the
    pipelined hot path overlaps host write-back/append work with the device
    step.  The synthetic actor half appends BENCH_AL_TICKS env ticks per
    learn step from a pregenerated frame pool — the host duty cycle of the
    real apex loop without env stepping noise.

    Toy-sized on purpose: the Atari-shape step takes seconds/step on CPU;
    the pipeline effect is a property of the LOOP, not the workload size."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.agents.agent import FrameStacker
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step, init_train_state
    from rainbow_iqn_apex_tpu.parallel.apex import ActorPriorityEstimator
    from rainbow_iqn_apex_tpu.replay.buffer import PrioritizedReplay
    from rainbow_iqn_apex_tpu.utils.prefetch import make_replay_prefetcher
    from rainbow_iqn_apex_tpu.utils.writeback import WritebackRing

    platform = jax.devices()[0].platform
    # Sized so the CPU device step lands in single-digit ms — the operating
    # operating point the TPU Atari-shape learner was sized for, where the
    # seed's per-step sync was the dominant tax.
    # The actor half per learn step is `ticks` env ticks of REAL host duty:
    # FrameStacker shift + replay append + ActorPriorityEstimator n-step TD.
    h = w = int(os.environ.get("BENCH_AL_FRAME", "44"))
    lanes = int(os.environ.get("BENCH_AL_LANES", "128"))
    ticks = int(os.environ.get("BENCH_AL_TICKS", "8"))
    iters = int(os.environ.get("BENCH_AL_ITERS", "80"))
    reps = int(os.environ.get("BENCH_AL_REPS", "3"))
    # per-tick emulated env latency (µs): real vector envs stall the actor
    # thread on subprocess/ALE IPC each tick (the reference's actors are
    # separate processes).  The sync loop serializes that stall behind the
    # per-step device round-trip; the pipelined loop absorbs it while the
    # in-flight step still executes.  Defaults keep the actor half (numpy
    # work + stall) just UNDER the device step so the pipelined loop is
    # device-bound — the Ape-X operating point the ring targets.
    env_us = int(os.environ.get("BENCH_AL_ENV_US", "500"))
    num_actions = 6
    cfg = Config().replace(
        compute_dtype="float32",
        frame_height=h,
        frame_width=w,
        history_length=2,
        hidden_size=32,
        num_cosines=8,
        num_tau_samples=4,
        num_tau_prime_samples=4,
        num_quantile_samples=4,
        batch_size=16,
        multi_step=3,
        prefetch_depth=2,
    )
    depth = int(os.environ.get("BENCH_AL_DEPTH", str(cfg.writeback_depth)))
    # NO buffer donation here: on the CPU backend a donated dispatch runs
    # SYNCHRONOUSLY (measured: each donated call blocks for its own
    # computation), which would serialize the loop at every depth and hide
    # the pipeline effect this row exists to measure.  Accelerator backends
    # dispatch donated calls asynchronously, so the production learn steps
    # keep donation (HBM in-place updates); the undonated toy step is the
    # CPU-side stand-in for that behaviour.
    learn = jax.jit(build_learn_step(cfg, num_actions))

    # pregenerated synthetic env ticks (frames/actions/rewards/cuts): the
    # measured host cost is the real pipeline work, not RNG
    rng = np.random.default_rng(0)
    pool = [
        (
            rng.integers(0, 255, (lanes, h, w), dtype=np.uint8),
            rng.integers(0, num_actions, lanes).astype(np.int64),
            rng.normal(size=lanes).astype(np.float32),
            (rng.random(lanes) < 0.01),
            rng.normal(size=(lanes, num_actions)).astype(np.float32),  # Q
        )
        for _ in range(16)
    ]

    def run(run_depth: int, run_iters: int) -> float:
        memory = PrioritizedReplay(
            1 << 15, (h, w), history=2, n_step=3, gamma=0.99, lanes=lanes,
            priority_exponent=0.5, seed=0,
        )
        stacker = FrameStacker(lanes, (h, w), 2)
        estimator = ActorPriorityEstimator(lanes, 3, 0.99)

        def actor_tick(t: int) -> None:
            f, a, r, d, q = pool[t % len(pool)]
            stacker.push(f)
            pri = estimator.push(q, a, r, d)
            memory.append_batch(f, a, r, d, pri)
            stacker.reset_lanes(d)

        def env_wait() -> None:
            # the tick loop's emulated env-IPC stalls, consolidated into one
            # sleep per learn step (sub-ms sleeps land on timer-slack
            # granularity under load, which would overstate the stall)
            if env_us:
                time.sleep(ticks * env_us / 1e6)

        for t in range(4096 // lanes + 8):  # prefill to sampleable
            actor_tick(t)
        state = init_train_state(cfg, num_actions, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        pf = make_replay_prefetcher(memory, cfg, lambda: 0.6)
        ring = WritebackRing(run_depth)
        try:
            for i in range(3):  # compile + warm the pipe
                idx, batch = pf.get()
                key, k = jax.random.split(key)
                state, info = learn(state, batch, k)
            jax.block_until_ready(info["loss"])
            n = 0
            t0 = time.perf_counter()
            for i in range(run_iters):
                env_wait()
                for t in range(ticks):  # the actor half of the loop
                    actor_tick(i * ticks + t)
                idx, batch = pf.get()
                key, k = jax.random.split(key)
                state, info = learn(state, batch, k)
                retired = ring.push(i + 1, idx, info)
                if retired is not None:
                    memory.update_priorities(retired.idx, retired.priorities)
                n = i + 1
                if left() < 15:
                    break
            for retired in ring.drain():
                memory.update_priorities(retired.idx, retired.priorities)
            jax.block_until_ready(info["loss"])
            return n / (time.perf_counter() - t0), n
        finally:
            pf.close()

    # Interleaved repetitions, best-of per mode (the timeit min-of-repeats
    # convention: the fastest repetition is the least-contended measurement
    # of the machine; slower ones measure the shared sandbox, not the loop).
    # Each repetition runs BOTH modes and alternates which goes first, so a
    # monotone slowdown penalizes the two modes equally; repetitions are
    # adaptive — both modes keep sampling, symmetrically, until neither
    # best-of improves by >2% (the uncontended value has been seen) or the
    # rep/budget cap is hit.
    max_reps = int(os.environ.get("BENCH_AL_MAX_REPS", "6"))
    r0, rk = [], []  # (steps_per_sec, iterations_measured) per repetition
    rep = 0
    while rep < max_reps and left() > 25:
        best_before = (max((s for s, _ in r0), default=0.0),
                       max((s for s, _ in rk), default=0.0))
        if depth == 0:
            # degenerate comparison (writeback_depth=0: the configured depth
            # IS the seed baseline) — one mode, speedup reported as 1.0
            r0.append(run(0, iters))
            rk = r0
        else:
            order = (0, depth) if rep % 2 == 0 else (depth, 0)
            for mode in order:
                (r0 if mode == 0 else rk).append(run(mode, iters))
                if left() < 20:
                    print("bench: apex_loop budget exhausted "
                          "mid-repetition", file=sys.stderr, flush=True)
                    break
        rep += 1
        if rep >= reps and r0 and rk:
            improved = (max(s for s, _ in r0) > best_before[0] * 1.02
                        or max(s for s, _ in rk) > best_before[1] * 1.02)
            if not improved:
                break
    if not rk:
        print("bench: budget exhausted after depth-0 apex_loop run",
              file=sys.stderr, flush=True)
        return []
    sps0 = max(s for s, _ in r0)
    sps_k = max(s for s, _ in rk)
    return [{
        "metric": "apex_loop_steps_per_sec",
        "value": round(sps_k, 2),
        "unit": (
            f"learn_steps/s (apex loop on {platform}: toy {h}x{w}x2 batch="
            f"{cfg.batch_size}, synthetic replay, {lanes}-lane x {ticks}-"
            f"tick actor half (stack+append+TD, {env_us}us emulated env "
            "IPC/tick), real sample + ring write-back; writeback_depth="
            f"{depth} vs 0)"
        ),
        "vs_baseline": None,  # toy shape — not comparable to the 75/s class
        "path": "apex_loop",
        "depth": depth,
        "depth0_steps_per_sec": round(sps0, 2),
        "speedup_vs_depth0": round(sps_k / max(sps0, 1e-9), 3),
        # ACTUAL iterations measured (budget breaks can truncate a rep —
        # downstream must not mistake a truncated sample for a full one)
        "n_iters": sum(n for _, n in rk),
        "reps": len(rk),
        "reps0": len(r0),
    }]


def _measure_device_replay(cfg, num_actions: int, left=None) -> dict | None:
    """Fused on-device PER learner at the reference Atari workload: 100k-frame
    HBM ring (16 lanes), prefilled in-graph by a lax.scan of appends (no host
    traffic), then timed over jitted 50-step lax.scan segments of the
    sample->learn->update tick.

    ``left`` (remaining soft-budget seconds) is checked between device calls;
    when it runs out the phase returns what it has (or None before the first
    timed segment) instead of being killed mid-RPC."""
    if left is None:
        left = lambda: float("inf")  # noqa: E731
    import jax
    import jax.numpy as jnp

    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.replay.device import DeviceReplay, build_device_learn

    # 100k frames ~ 0.7 GB uint8 in HBM (env knobs exist so tests can run
    # the same code path at toy sizes on CPU)
    lanes = int(os.environ.get("BENCH_DR_LANES", "16"))
    seg = int(os.environ.get("BENCH_DR_SEG", "6250"))
    h, w = cfg.frame_height, cfg.frame_width
    replay = DeviceReplay(
        lanes=lanes, seg=seg, frame_shape=(h, w),
        history=cfg.history_length, n_step=cfg.multi_step, gamma=cfg.gamma,
        priority_exponent=cfg.priority_exponent, priority_eps=cfg.priority_eps,
    )

    def prefill_tick(ds, key):
        kf, ka, kr, kp, kt = jax.random.split(key, 5)
        ds = replay.append(
            ds,
            jax.random.randint(kf, (lanes, h, w), 0, 255, jnp.uint8),
            jax.random.randint(ka, (lanes,), 0, num_actions, jnp.int32),
            jax.random.normal(kr, (lanes,)),
            jax.random.bernoulli(kt, 0.005, (lanes,)),
            jnp.zeros((lanes,), bool),
            jax.random.uniform(kp, (lanes,)) + 0.05,
        )
        return ds, None

    @functools.partial(jax.jit, donate_argnums=0)
    def prefill(ds, key):
        keys = jax.random.split(key, seg)
        ds, _ = jax.lax.scan(prefill_tick, ds, keys)
        return ds

    ds = prefill(replay.init_state(), jax.random.PRNGKey(7))
    jax.block_until_ready(ds.priority)
    print(f"bench: device replay prefilled, {left():.0f}s left",
          file=sys.stderr, flush=True)
    if left() < 60:  # segment compile + first run still ahead
        print("bench: budget exhausted after prefill, skipping",
              file=sys.stderr, flush=True)
        return None

    ts = init_train_state(cfg, num_actions, jax.random.PRNGKey(0))
    fused = build_device_learn(cfg, num_actions, replay)
    SCAN = int(os.environ.get("BENCH_DR_SCAN", "50"))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def segment(ts, ds, key):
        def tick(carry, k):
            ts, ds = carry
            ts, ds, info = fused(ts, ds, k, jnp.float32(0.5))
            return (ts, ds), info["loss"]

        (ts, ds), losses = jax.lax.scan(tick, (ts, ds), jax.random.split(key, SCAN))
        return ts, ds, losses[-1]

    key = jax.random.PRNGKey(1)
    key, k = jax.random.split(key)
    ts, ds, last = segment(ts, ds, k)  # compile + warm
    jax.block_until_ready(last)
    print(f"bench: fused segment compiled, {left():.0f}s left",
          file=sys.stderr, flush=True)
    if left() < 20:
        print("bench: budget exhausted after segment compile, skipping",
              file=sys.stderr, flush=True)
        return None
    max_segments = int(os.environ.get("BENCH_DR_SEGMENTS", "8"))
    t0 = time.perf_counter()
    segments = 0
    while segments < max_segments and (segments < 1 or left() > 20):
        key, k = jax.random.split(key)
        ts, ds, last = segment(ts, ds, k)
        # sync before the budget check: dispatch is async, only device
        # completion spends real time (donation serialises segments anyway)
        jax.block_until_ready(last)
        segments += 1
    dt = time.perf_counter() - t0
    sps = segments * SCAN / dt
    platform = jax.devices()[0].platform
    return {
        "metric": "iqn_learner_steps_per_sec_atari_shape",
        "value": round(sps, 2),
        "unit": (
            f"learn_steps/s (batch={cfg.batch_size}, {h}x{w}x"
            f"{cfg.history_length}, N=N'={cfg.num_tau_samples}, {platform}; "
            f"device-resident PER replay {lanes * seg // 1000}k frames, "
            "sampling + priority write-back in-graph)"
        ),
        "vs_baseline": round(sps / 75.0, 3),
        "path": "device_replay",
    }


def main() -> int:
    from rainbow_iqn_apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    measure()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
