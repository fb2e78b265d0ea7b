#!/usr/bin/env python
"""Device-resident learn-step microbenchmark (batch pre-staged in HBM).

Times the full jitted IQN learn step at the reference Atari shape
(SURVEY §3.4: batch 32, 84x84x4, N=N'=64) with the batch already on
device, so the number isolates pure learn-step dispatch+compute from the
host-feed pipeline that bench.py measures.  One JSON line per row:

    python scripts/bench_learn_micro.py           # the device JAX gives it
    BENCH_ITERS=50 python scripts/bench_learn_micro.py

History: this file (as bench_pallas.py) once compared the jnp
quantile-Huber loss against a hand-written Pallas kernel.  That kernel
was never compiled on a chip, so the comparison was never made (not
measured); the kernel was deleted and this harness times the jnp path.
"""

import json
import os
import sys
import time
from typing import Callable, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_learn(
    iters: int,
    stop: Optional[Callable[[], bool]] = None,
) -> dict:
    """Timed full-learn-step loop at the reference Atari shape.

    ``stop`` lets a caller impose a soft wall-clock budget; a run cut
    short reports the iterations it actually completed, and a run with
    ZERO timed iterations reports ``skipped`` instead of a rate.
    """
    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.agents.agent import to_device_batch
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step, init_train_state
    from rainbow_iqn_apex_tpu.replay.buffer import SampledBatch

    platform = jax.devices()[0].platform
    cfg = Config()
    num_actions = 18
    rng = np.random.default_rng(0)
    state = init_train_state(cfg, num_actions, jax.random.PRNGKey(0))
    learn = jax.jit(build_learn_step(cfg, num_actions), donate_argnums=0)
    b = cfg.batch_size
    batch = to_device_batch(SampledBatch(
        idx=np.arange(b),
        obs=rng.integers(0, 255, (b, *cfg.state_shape), dtype=np.uint8),
        action=rng.integers(0, num_actions, b).astype(np.int32),
        reward=rng.normal(size=b).astype(np.float32),
        next_obs=rng.integers(0, 255, (b, *cfg.state_shape), dtype=np.uint8),
        discount=np.full(b, 0.99**3, np.float32),
        weight=np.ones(b, np.float32),
        prob=np.full(b, 1.0 / b),
    ))
    key = jax.random.PRNGKey(1)
    for _ in range(2):  # compile + warm
        key, k = jax.random.split(key)
        state, info = learn(state, batch, k)
    jax.block_until_ready(info["loss"])
    row = {"loss_impl": "jnp", "platform": platform}
    t0 = time.perf_counter()
    n = 0
    while n < iters and not (stop is not None and stop()):
        key, k = jax.random.split(key)
        state, info = learn(state, batch, k)
        n += 1
    jax.block_until_ready(info["loss"])
    dt = time.perf_counter() - t0
    if n == 0:
        return {**row, "skipped": "budget exhausted before any timed iteration"}
    return {**row, "steps_per_sec": round(n / dt, 2), "iters": n,
            "loss": float(info["loss"])}


def main() -> None:
    import jax

    on_accel = jax.default_backend() == "tpu"
    iters = int(os.environ.get("BENCH_ITERS", "100" if on_accel else "3"))
    print(json.dumps(measure_learn(iters)))


if __name__ == "__main__":
    main()
