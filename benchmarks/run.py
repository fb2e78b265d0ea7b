"""python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--keep-trace]

One run of one cell of BENCHMARK.json on the TPU this process is started on.
Prints one JSON object as the last line of standard output; see
benchmarks/harness.py.  Exits non-zero, printing no result, where JAX finds
no TPU or fewer chips than the cell asks for.
"""

import time

T0 = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave a --trace 1 run's profile under "
                         "chiprun_out/benchmarks/<cell>/trace")
    args = ap.parse_args()
    from benchmarks import harness

    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t0=T0, keep_trace=args.keep_trace)


if __name__ == "__main__":
    sys.exit(main())
