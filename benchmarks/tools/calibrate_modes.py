"""`calibrate.py` for a driver whose `reference_side` takes faults of its own
beside the arithmetic modes: reads, on the chip at a cell's own size, the
numbers `correct` compares, for sound runs of the program over many seeds and
for each of `--modes` (the reference in that mode, put in the program's
place) on the first `--first` seeds.  One process, one compile a mode.

    python3 benchmarks/tools/calibrate_modes.py <cell> --seeds 1,2,3 \\
        --modes fp8,ignore_span --first 3

Prints one JSON line per seed: {"seed", "steps", "sound": {...}, "<mode>":
{...}, "<mode>_correct": false, ...}.
"""

import argparse
import gc
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="")
    ap.add_argument("--first", type=int, default=0,
                    help="run the modes on the first N seeds")
    args = ap.parse_args()
    from benchmarks import check, harness

    wl, cfg, traffic = harness.load_cell(args.cell)
    if harness.device_gate(int(wl["chips"])) is None:
        return 3
    harness.enable_cache()
    make = importlib.import_module("benchmarks.drivers." + cfg["driver"]).Driver
    modes = [m for m in args.modes.split(",") if m]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        drv = make(cfg["fields"], traffic, seed, int(wl["chips"]))
        drv.warm_up()
        drv.free()
        prog = drv.program_side()
        ref = drv.reference_side(
            None, prog["priority_after"] != drv.priority0())
        row = {"seed": seed, "steps": drv.first_learning["steps"],
               "counters": dict(getattr(drv, "counters", {})),
               "sound": check.compare(prog, ref, drv.params0)}
        for mode in modes if i < args.first else ():
            ctrl = drv.reference_side(mode, None)
            row[mode] = check.compare(ctrl, ref, drv.params0)
            row[mode + "_correct"] = check.verdict(
                {**row[mode], "first_steps_missing": 0.0,
                 "window_steps_missing": 0.0}, wl["limits"],
                wl.get("read_not_compared", ()))[0]
            del ctrl
        print(json.dumps(row), flush=True)
        del drv, prog, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
