#!/usr/bin/env python
"""Per-level generalization re-eval of an EXISTING variant checkpoint
(VERDICT r4 item 4, for checkpoints that predate the per_level block).

Evaluates the checkpoint with lanes pinned to each of the 16 train levels
and to --levels held-out levels (ids 16..16+levels-1), then writes/updates
results/jaxsuite/generalization_levels.json with per-level means,
across-level spread, and the level-bootstrap gap-sign stability — keyed by
game, with explicit checkpoint provenance (run id + step), because the
re-evaluated checkpoint may not be the one behind the committed two-pool
row in generalization.json.

Example (the round-3 16.4k-frame variant checkpoints):
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
  python scripts/eval_gen_levels.py --game freeway --run-id jaxsuite_freeway_var \
    --checkpoint-dir results/jaxsuite/ckpt -- \
    --role anakin --history-length 2 --compute-dtype float32
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--game", required=True,
                    help="base game name (must have a seeded-variant mode)")
    ap.add_argument("--run-id", required=True)
    ap.add_argument("--checkpoint-dir", default="results/jaxsuite/ckpt")
    ap.add_argument("--levels", type=int, default=64)
    ap.add_argument("--eps-per-level", type=int, default=8)
    ap.add_argument("--out", default="results/jaxsuite/generalization_levels.json")
    args, passthrough = ap.parse_known_args()
    if passthrough and passthrough[0] == "--":
        passthrough = passthrough[1:]

    from rainbow_iqn_apex_tpu.envs.device_games import N_TRAIN_LEVELS
    from rainbow_iqn_apex_tpu.jaxsuite import (
        eval_checkpoint_per_level,
        per_level_fields,
    )
    from rainbow_iqn_apex_tpu.utils.checkpoint import Checkpointer

    base_args = [*passthrough, "--checkpoint-dir", args.checkpoint_dir]
    step = Checkpointer(
        os.path.join(args.checkpoint_dir, args.run_id)).latest_step()
    # one call over both pools = one compile + one checkpoint restore
    all_pl = eval_checkpoint_per_level(
        base_args, args.run_id, args.game,
        range(N_TRAIN_LEVELS + args.levels), args.eps_per_level)
    train_pl, held_pl = all_pl[:N_TRAIN_LEVELS], all_pl[N_TRAIN_LEVELS:]
    row = {
        "checkpoint": {"run_id": args.run_id, "step": step,
                       "dir": args.checkpoint_dir},
        **per_level_fields(train_pl, held_pl, N_TRAIN_LEVELS),
    }
    data = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            data = json.load(f)
    data[args.game] = row
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(data, f, indent=2)
    print(json.dumps({args.game: {k: row[k] for k in
                                  ("train_mean", "heldout_mean", "gap",
                                   "gap_boot_frac_positive",
                                   "gap_boot_ci90")}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
