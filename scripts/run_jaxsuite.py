#!/usr/bin/env python
"""Run the pure-JAX game benchmark suite end-to-end.

Trains each game via the training CLI with the flags you pass through,
evals, measures random/scripted baselines, and writes
results/jaxsuite/{per_game.csv, aggregate.json}.  One process for each chip:
the training children run one at a time on the device the caller's
environment gives them, and this parent pins itself to the CPU backend for
its baselines and salvage math (atari57.pin_sweep_parent_to_cpu).

Example (CPU sandbox, short budget):
  python scripts/run_jaxsuite.py --games catch breakout -- \
    --role anakin --t-max 8000 --learn-start 512 --frames-per-learn 2 \
    --history-length 2 --gamma 0.9 --memory-capacity 8192 \
    --learning-rate 1e-3 --target-update-period 200 \
    --compute-dtype float32 --eval-episodes 40

Everything after `--` goes verbatim to train_agent_apex.py.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rainbow_iqn_apex_tpu.jaxsuite import JAXSUITE, run_sweep  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--games", nargs="*", default=None, choices=JAXSUITE,
                    help="subset of games (default: all)")
    ap.add_argument("--results-dir", default="results/jaxsuite")
    ap.add_argument("--baseline-episodes", type=int, default=64)
    ap.add_argument("--generalization", action="store_true",
                    help="instead of the score sweep, run the seeded-variant "
                         "train/held-out level split (writes "
                         "generalization.json)")
    ap.add_argument("--levels-eval", type=int, default=64,
                    help="generalization mode: per-level eval over this many "
                         "held-out levels (0 disables the per_level block)")
    ap.add_argument("--eps-per-level", type=int, default=8,
                    help="episodes per pinned level in the per-level eval")
    ap.add_argument("--note", default=None,
                    help="free-text caveat emitted into aggregate.json by the "
                         "writer itself (survives reruns)")
    ap.add_argument("--resume-rows", action="store_true",
                    help="score sweep: seed per_game.csv/aggregate.json from "
                         "the existing rows of games NOT in --games, so "
                         "rerunning a killed sweep's unfinished games keeps "
                         "the finished games' committed rows")
    ap.add_argument("--per-game-t-max", nargs="*", default=[],
                    metavar="GAME=FRAMES",
                    help="per-game --t-max override, e.g. breakout=65536 "
                         "(slow-to-learn games get a bigger budget than the "
                         "shared flags)")
    args, passthrough = ap.parse_known_args()
    if passthrough and passthrough[0] == "--":
        passthrough = passthrough[1:]
    per_game_args = {}
    for spec in args.per_game_t_max:
        game, _, frames = spec.partition("=")
        if not frames.isdigit():
            ap.error(f"--per-game-t-max wants GAME=FRAMES, got {spec!r}")
        if game not in JAXSUITE:
            # fail fast: a typo'd name would otherwise silently train the
            # game at the shared budget for hours (overrides are keyed by
            # BASE name in both modes — no '@var' suffix)
            ap.error(f"--per-game-t-max: unknown game {game!r} "
                     f"(have: {', '.join(JAXSUITE)})")
        per_game_args[game] = ["--t-max", frames]
    if args.generalization:
        from rainbow_iqn_apex_tpu.jaxsuite import run_generalization

        out = run_generalization(passthrough, games=args.games,
                                 results_dir=args.results_dir,
                                 episodes=args.baseline_episodes,
                                 per_game_args=per_game_args, note=args.note,
                                 levels_eval=args.levels_eval,
                                 episodes_per_level=args.eps_per_level)
        print(json.dumps(out))
        return 0
    agg = run_sweep(passthrough, games=args.games,
                    results_dir=args.results_dir,
                    baseline_episodes=args.baseline_episodes,
                    per_game_args=per_game_args, note=args.note,
                    resume_rows=args.resume_rows)
    print(json.dumps(agg))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
