"""Run one benchmark cell on the chip.

    python3 bench/run.py --workload long_ont.offline --seed 7 \\
        --seconds 30 --trace 0

Prints information lines, then the result as the last line of standard
output; the numbers of the correctness check go last on standard error.
Exits 1, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control-bits", type=int, default=None,
                    help="judge the control (the reference saturated to "
                    "this many bits) in place of the service's answers; "
                    "its result must not be correct")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        harness.run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         control_bits=args.control_bits)
    except harness.NoAccelerator as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
