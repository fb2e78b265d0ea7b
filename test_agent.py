#!/usr/bin/env python
"""Evaluation entry point (name kept for parity with the reference's
`test_agent.py`, BASELINE.json:5 / SURVEY.md §3.5): load a checkpoint, run
SABER-protocol eval episodes, print score statistics as JSON.  A run id with
no checkpoint under it is an error, never an evaluation of a fresh net."""

import json
import os

import jax

from rainbow_iqn_apex_tpu.agents.agent import Agent
from rainbow_iqn_apex_tpu.config import parse_config
from rainbow_iqn_apex_tpu.envs import make_env
from rainbow_iqn_apex_tpu.eval import evaluate
from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer
from rainbow_iqn_apex_tpu.utils.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    cfg = parse_config(argv)
    enable_compile_cache()
    ckpt_dir = os.path.join(cfg.checkpoint_dir, cfg.run_id)
    # isdir first: Checkpointer creates its directory, and a mistyped run id
    # must not leave an empty one behind
    ckpt = Checkpointer(ckpt_dir) if os.path.isdir(ckpt_dir) else None
    if ckpt is None or ckpt.latest_step() is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    env = make_env(cfg.env_id, seed=cfg.seed)
    if cfg.architecture == "r2d2":
        from rainbow_iqn_apex_tpu.train_r2d2 import R2D2Agent, evaluate_r2d2

        agent = R2D2Agent(
            cfg, env.num_actions, env.frame_shape,
            jax.random.PRNGKey(cfg.seed), train=False,
        )
        eval_fn = lambda: evaluate_r2d2(cfg, agent, seed=cfg.seed + 977)  # noqa: E731
    else:
        agent = Agent(
            cfg,
            env.num_actions,
            jax.random.PRNGKey(cfg.seed),
            train=False,
            state_shape=(*env.frame_shape, cfg.history_length),
        )
        eval_fn = lambda: evaluate(cfg, agent, seed=cfg.seed + 977)  # noqa: E731

    agent.state, _ = ckpt.restore(agent.state)
    out = eval_fn()
    out["checkpoint_step"] = ckpt.latest_step()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
