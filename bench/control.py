"""Readings of a cell's control and of the faults planted in its
reference, for setting and re-checking the limits of ``correct``.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed it prints one JSON line: the control (the reference one
precision step below what the configuration states) and each fault that
the cell can have, each compared with the reference by the same numbers
that decide ``correct``, beside the cell's limits.  The benchmark's own
runs never run this; it needs the chip the cell asks for, and no run of
the program.
"""
import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--no-faults", action="store_true")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from bench import harness

    cell = harness.load_cell(args.workload, root)
    try:
        harness.require_accelerator(cell.chips)
    except harness.NoAccelerator as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        loop = harness.loop_module(cell).Loop(cell, seed, harness.span)
        readings = loop.control_readings(faults=not args.no_faults)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "limits": cell.limits, "readings": readings,
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
