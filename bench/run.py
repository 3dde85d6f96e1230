"""Chip benchmark of the OTA-DSGD system: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights and inputs made on the device from the seed, the cell's
programs compiled or loaded from the persistent cache, and the first
steps driven and recorded for the correctness check) is timed as
``setup_s``; then the cell's loop runs for ``--seconds``.  With
``--trace 1`` the window runs under the profiler and the per-layer metrics
are printed in place of the end-to-end ones.  After the window the
program's state is freed and the plain reference checks what the timed
path produced.  The last line of stdout is one JSON object; the numbers
compared, each beside its limit, end stderr and the line's ``checks``.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 1 and prints no result.
"""
import argparse
import os
import sys
import time


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        print(f"run.py: no repro package under {root}/src; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, root)
    from bench import harness

    try:
        return harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start, root)
    except harness.NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
