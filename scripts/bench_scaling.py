#!/usr/bin/env python
"""Batch-scaling study of the device-resident PER learner.

For each batch size, builds the fused sample->learn->write-back graph
(replay/device.py) at the reference Atari workload shape, times jitted
50-step lax.scan segments, and reports steps/s, samples/s (consumed
transitions/s) and ms/step.  A soft internal budget is checked between
device calls.  It reports no utilization: the FLOPs of a learn step come
from the shape functions of benchmarks/flops.py and the chip's peaks from
benchmarks/peaks.json (`learn_mfu`, PERF.md), not from XLA's
cost_analysis().

Usage: python scripts/bench_scaling.py [total_budget_seconds=420] [batches]
       e.g. python scripts/bench_scaling.py 420 32,64,128,256
Writes one JSON line per batch point.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGET = float(sys.argv[1]) if len(sys.argv) > 1 else 420.0


def _parse_point(tok: str):
    """'128' -> (128, 1); '32x4' -> (32, 4): batch_size x sample_groups —
    the grouped-draw learner (replay/device.sample_grouped) that keeps the
    reference's batch-32 PER stratum width while feeding the MXU a G*B
    GEMM."""
    if "x" in tok:
        b, g = tok.split("x", 1)
        return int(b), int(g)
    return int(tok), 1


BATCHES = [_parse_point(b) for b in
           (sys.argv[2] if len(sys.argv) > 2
            else "32,64,128,256,32x2,32x4").split(",")]
T0 = time.monotonic()


def left() -> float:
    return BUDGET - (time.monotonic() - T0)


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import init_train_state
    from rainbow_iqn_apex_tpu.replay.device import DeviceReplay, build_device_learn

    platform = jax.devices()[0].platform
    kind = jax.devices()[0].device_kind
    emit(phase="hello", platform=platform, device_kind=kind,
         device_count=len(jax.devices()), budget_s=BUDGET, batches=BATCHES)

    A = 18
    lanes = int(os.environ.get("SCALE_LANES", "16"))
    seg = int(os.environ.get("SCALE_SEG", "2048"))  # 32k-frame ring
    SCAN = int(os.environ.get("SCALE_SCAN", "50"))

    base = Config()
    h, w = base.frame_height, base.frame_width
    replay = DeviceReplay(
        lanes=lanes, seg=seg, frame_shape=(h, w),
        history=base.history_length, n_step=base.multi_step, gamma=base.gamma,
        priority_exponent=base.priority_exponent,
        priority_eps=base.priority_eps,
    )

    # prefill once; every batch point samples from the same warm ring
    def prefill_tick(ds, key):
        kf, ka, kr, kp, kt = jax.random.split(key, 5)
        ds = replay.append(
            ds,
            jax.random.randint(kf, (lanes, h, w), 0, 255, jnp.uint8),
            jax.random.randint(ka, (lanes,), 0, A, jnp.int32),
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

    ds0 = prefill(replay.init_state(), jax.random.PRNGKey(7))
    jax.block_until_ready(ds0.priority)
    emit(phase="prefill", frames=lanes * seg, left_s=round(left(), 1))

    for b, groups in BATCHES:
        label = f"{b}x{groups}" if groups > 1 else str(b)
        if left() < 90:
            emit(phase="scale", batch=label, skipped="budget exhausted")
            continue
        cfg = base.replace(batch_size=b, sample_groups=groups)
        ts = init_train_state(cfg, A, jax.random.PRNGKey(0))
        fused = build_device_learn(cfg, A, replay)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def segment(ts, ds, key, fused=fused):
            # ds rides the scan carry so the priority write-back stays live
            # (dropping it would let XLA DCE update_priorities out of the
            # measurement).  ds0 itself is NOT donated — every batch point
            # reuses the same warm ring; the one ring copy this costs per
            # segment call amortises to microseconds/step.
            def tick(carry, k):
                ts, ds = carry
                ts, ds, info = fused(ts, ds, k, jnp.float32(0.5))
                return (ts, ds), info["loss"]

            (ts, _ds), losses = jax.lax.scan(
                tick, (ts, ds), jax.random.split(key, SCAN)
            )
            return ts, losses[-1]

        key = jax.random.PRNGKey(2)
        key, k = jax.random.split(key)
        ts, last = segment(ts, ds0, k)
        jax.block_until_ready(last)
        if left() < 30:
            emit(phase="scale", batch=label,
                 skipped="budget exhausted post-compile")
            continue
        n_seg = 0
        t0 = time.perf_counter()
        while n_seg < 6 and (n_seg < 1 or left() > 30):
            key, k = jax.random.split(key)
            ts, last = segment(ts, ds0, k)
            jax.block_until_ready(last)
            n_seg += 1
        dt = time.perf_counter() - t0
        sps = n_seg * SCAN / dt
        emit(
            phase="scale",
            batch=label,
            steps_per_sec=round(sps, 2),
            samples_per_sec=round(sps * b * groups, 1),
            ms_per_step=round(1e3 / sps, 3),
            platform=platform,
            device_kind=kind,
        )

    emit(phase="done", elapsed_s=round(time.monotonic() - T0, 1))


if __name__ == "__main__":
    main()
