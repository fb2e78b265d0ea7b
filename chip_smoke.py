#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the CLIs' own ``main``
functions, at ``Config``'s default (reference) widths — hidden 512, 64
cosines, N=N'=64, K=32, batch 32, history 4, bf16 compute — on the envs'
native 80x80 frames, from seeded random weights:

  anakin   train_agent_apex.main --role anakin --env-id jaxgame:breakout
           (fused env + HBM replay + sample -> learn -> write-back graph;
           writes an Orbax checkpoint)
  eval     test_agent.main on that checkpoint
  apex     train_agent_apex.main --role apex --env-id toy:catch (host env
           lanes, native replay core, prefetch, write-back ring, lane-sharded
           actor step, cross-mesh weight publish)
  core     train_agent_apex.main --architecture r2d2 --role anakin with
           --core-config at the published widths (the Kimi-Linear recurrent
           core, configs/cores/): a few dispatches of the fused R2D2 segment,
           finite loss, no token dropped by the expert layers
  reference  the checkpoint's Q-values on 8 fixed frames, chip at the
           configured bf16 against float32 on the host CPU backend

It sets no JAX_PLATFORMS, refuses to run unless JAX's first device is a TPU,
uses every chip it sees through the defaults (``learner_devices=0``), and
exits 0 only if every phase ran and every check held.  The last stdout line
is then ``{"ok": true, "device": {...}}`` with the device as JAX reports it.
Wall times, compile counts and peak HBM printed on the way are smoke
observations, not benchmark numbers.

Run it from a parent that has not touched JAX: a chip belongs to one process.
"""

import contextlib
import io
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")

# Sizes are the smoke's own; widths are Config's defaults and stay untouched.
EVAL_EPISODES = 5
COMMON = [
    "--results-dir", os.path.join(OUT, "results"),
    "--checkpoint-dir", os.path.join(OUT, "checkpoints"),
    "--eval-interval", "0", "--eval-episodes", str(EVAL_EPISODES),
    "--seed", "21",
]
ANAKIN = [
    "--role", "anakin", "--env-id", "jaxgame:breakout", "--run-id", "anakin",
    "--memory-capacity", "262144", "--learn-start", "4096",
    "--t-max", "16384", "--metrics-interval", "256", *COMMON,
]
EVAL = ["--env-id", "jaxgame:breakout", "--run-id", "anakin", *COMMON]
APEX = [
    "--role", "apex", "--env-id", "toy:catch", "--run-id", "apex",
    "--memory-capacity", "131072", "--learn-start", "2048",
    "--t-max", "6144", "--metrics-interval", "100",
    "--weight-publish-interval", "100", *COMMON,
]
CORE = [
    "--architecture", "r2d2", "--role", "anakin", "--env-id",
    "jaxgame:freeway", "--run-id", "core",
    "--core-config", "configs/cores/kimi_linear_48b_a3b.json",
    "--num-envs-per-actor", "16", "--memory-capacity", "24576",
    "--learn-start", "1920", "--t-max", "4096", "--metrics-interval", "1",
    "--checkpoint-interval", "0", *COMMON,
]


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def read_rows(run_id: str) -> list:
    path = os.path.join(OUT, "results", run_id, "metrics.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def is_finite(x) -> bool:
    # MetricsLogger writes NaN as null and +/-inf as strings
    return isinstance(x, (int, float)) and math.isfinite(x)


def check_rows(rows: list, failures: list, phase: str) -> None:
    learn = [r for r in rows if r["kind"] == "learn"]
    if not learn:
        failures.append(f"{phase}: no learn row in metrics.jsonl")
    bad = [r["step"] for r in learn if not is_finite(r.get("loss"))]
    if bad:
        failures.append(f"{phase}: non-finite loss at steps {bad}")
    sick = [(r["step"], r["status"]) for r in rows
            if r["kind"] == "health" and r["status"] != "ok"]
    if sick:
        failures.append(f"{phase}: health rows not ok: {sick}")
    declined = [r["event"] for r in rows
                if r["kind"] == "notice" and r["event"].endswith("_fallback")]
    if declined:
        failures.append(f"{phase}: fallback notices: {declined}")


def peak_hbm(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def run_cli(main, argv: list) -> dict:
    """Call a CLI's main(argv); returns the JSON summary it prints last."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    if rc != 0:
        raise RuntimeError(f"{main.__module__}.main returned {rc}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def reference_check(failures: list) -> dict:
    """Q-values of the anakin checkpoint on 8 fixed frames: the chip at the
    configured compute dtype against float32 on the host CPU backend."""
    import jax
    import numpy as np

    from rainbow_iqn_apex_tpu.config import parse_config
    from rainbow_iqn_apex_tpu.envs.device_games import make_device_game
    from rainbow_iqn_apex_tpu.ops.learn import build_act_step, init_train_state
    from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer

    cfg = parse_config(EVAL)
    game = make_device_game("breakout")
    shape = (*game.frame_shape, cfg.history_length)
    template = init_train_state(
        cfg, game.num_actions, jax.random.PRNGKey(0), state_shape=shape)
    ckpt = Checkpointer(os.path.join(OUT, "checkpoints", "anakin"))
    state, _ = ckpt.restore(template)
    ckpt.close()
    obs = np.random.default_rng(0).integers(0, 255, (8, *shape), dtype=np.uint8)
    key = np.asarray(jax.random.PRNGKey(1))

    def q_values(c, device):
        act = jax.jit(build_act_step(c, game.num_actions, use_noise=False))
        args = jax.device_put((state.params, obs, key), device)
        return np.asarray(act(*args)[1], np.float32)

    q_chip = q_values(cfg, jax.devices()[0])
    q_ref = q_values(cfg.replace(compute_dtype="float32"), jax.devices("cpu")[0])
    # bfloat16 keeps 8 significand bits; a handful of layers deep, allow 16 ulp
    # of the largest Q-value
    tol = 16 * 2.0 ** -8 * max(1.0, float(np.abs(q_ref).max()))
    err = float(np.abs(q_chip - q_ref).max())
    if q_chip.shape != (8, game.num_actions) or not np.isfinite(q_chip).all():
        failures.append(f"reference: chip Q-values malformed, shape {q_chip.shape}")
    elif err > tol:
        failures.append(f"reference: max |q_chip - q_ref| {err:.4g} > {tol:.4g}")
    return {"reference": "float32 on the host CPU backend",
            "compute_dtype": cfg.compute_dtype,
            "max_abs_err": err, "tolerance": tol,
            "q_ref_abs_max": float(np.abs(q_ref).max())}


def main() -> int:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on platform "
              f"{platform!r} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
              " — nothing was run", file=sys.stderr)
        return 1
    return run(devices)


def run(devices) -> int:
    import importlib.metadata as md

    import jax

    # the program first: without it (this file alone in a directory) the run
    # ends here, before anything reaches stdout
    sys.path.insert(0, HERE)
    import test_agent
    import train_agent_apex
    from rainbow_iqn_apex_tpu.replay import native
    from rainbow_iqn_apex_tpu.utils.compile_cache import enable_compile_cache

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} "
        f"jaxlib={md.version('jaxlib')} libtpu={md.version('libtpu')}")
    log(f"compile cache: {enable_compile_cache()}")
    shutil.rmtree(OUT, ignore_errors=True)  # no stale checkpoint can pass eval
    os.makedirs(OUT)

    # persistent-cache traffic, counted at the source: the `compiles` figure in
    # the timing rows counts every compile-related event, hits included
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    failures: list = []
    report = {"device": device, "phases": {}}

    def phase(name: str, fn, *args):
        seen = dict(cache)
        t0 = time.time()
        log(f"phase {name} ...")
        try:
            out = fn(*args)
        except Exception as e:  # noqa: BLE001 — report every phase, then fail
            import traceback

            traceback.print_exc()
            failures.append(f"{name}: raised {e!r}")
            out = None
        row = {
            "wall_s": round(time.time() - t0, 1),
            "cache_hits": cache["hits"] - seen["hits"],
            "cache_misses": cache["misses"] - seen["misses"],
            "peak_hbm_bytes_so_far": peak_hbm(devices),
        }
        report["phases"][name] = row
        return out, row, t0

    def train_phase(name: str, argv: list):
        summary, row, t0 = phase(name, run_cli, train_agent_apex.main, argv)
        if summary is None:
            return None
        rows = read_rows(name)
        check_rows(rows, failures, name)
        if not summary.get("learn_steps", 0) > 0:
            failures.append(f"{name}: learn_steps={summary.get('learn_steps')}")
        first = next((r for r in rows if r["kind"] == "learn"), None)
        timing = [r for r in rows if r["kind"] == "timing"]
        row.update(
            learn_steps=summary.get("learn_steps"), frames=summary.get("frames"),
            first_learn_row_s=round(first["ts"] - t0, 1) if first else None,
            first_learn_row_step=first["step"] if first else None,
            compiles=timing[-1]["compiles"] if timing else None,
            last_loss=[r["loss"] for r in rows if r["kind"] == "learn"][-1:],
        )
        return summary

    anakin = train_phase("anakin", ANAKIN)

    evald, row, _ = phase("eval", run_cli, test_agent.main, EVAL)
    if evald is not None:
        row.update(checkpoint_step=evald.get("checkpoint_step"),
                   score_mean=evald.get("score_mean"))
        if anakin is None or evald.get("checkpoint_step") != anakin["learn_steps"]:
            failures.append(
                f"eval: checkpoint_step {evald.get('checkpoint_step')} != "
                f"anakin learn_steps {anakin and anakin['learn_steps']}")
        if (evald.get("episodes") != EVAL_EPISODES
                or not is_finite(evald.get("score_mean"))):
            failures.append(f"eval: malformed result {evald}")

    apex = train_phase("apex", APEX)
    if apex is not None:
        row = report["phases"]["apex"]
        row["rollbacks"] = apex.get("rollbacks")
        if apex.get("rollbacks") != 0:
            failures.append(f"apex: rollbacks={apex.get('rollbacks')}")
        # use_native_sumtree defaults to True: the C++ core, built on this host
        lib = native.loaded_library()
        row["replay_core"] = f"native ({lib})" if lib else "numpy"
        if lib is None or not lib.startswith(HERE + os.sep):
            failures.append(f"apex: configured the native replay core, ran {lib}")

    if train_phase("core", CORE) is not None:
        learn = [r for r in read_rows("core") if r["kind"] == "learn"]
        dropped = [r.get("moe_tokens_dropped") for r in learn]
        report["phases"]["core"].update(
            moe_tokens_dropped=max(dropped, default=None),
            moe_held_assign_share=[r.get("moe_held_assign_share")
                                   for r in learn][-1:])
        if not learn or any(d != 0 for d in dropped):
            failures.append(f"core: moe_tokens_dropped {dropped}")

    ref, row, _ = phase("reference", reference_check, failures)
    if ref is not None:
        row.update(ref)

    for name, row in report["phases"].items():
        log(f"{name}: {json.dumps(row)}")
    report["failures"] = failures
    with open(os.path.join(OUT, "report.json"), "w") as f:
        json.dump(report, f, indent=2)
    if failures:
        for msg in failures:
            print(f"chip_smoke: FAILED {msg}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
