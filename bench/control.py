#!/usr/bin/env python3
"""Read the control and the planted faults of one cell on the chip.

    python3 bench/control.py --workload rgg-mesh.offline --seeds 11,12,13 \\
        --seconds 2 --what control,unchanged,half,altered

Runs, in one process, a whole run of the cell per seed with the program
replaced by :func:`bench.faults.control` (``control``) or by a planted fault
(``unchanged``, ``half``, ``altered``), and prints one JSON line per run
with ``correct`` and every number compared.  The benchmark's own runs never
run this: it gives the upper readings that the limits were set below
(PERF.md), and shows that each comes out not correct at the cell's size.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--what", default="control")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import drive, faults, harness

    harness.enable_cache()
    prog = drive.program()
    for what in args.what.split(","):
        broken = (faults.control(prog) if what == "control"
                  else faults.offline_fault(prog, what))
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            try:
                out = harness.run_cell(args.workload, seed, args.seconds, False,
                                       t_start=t0, root=ROOT, prog=broken)
            except harness.NoDevice as e:
                print(f"bench: {e}", file=sys.stderr)
                return 2
            print(json.dumps({"what": what, "seed": seed, "correct": out["correct"],
                              "attempted": out["attempted"], "failed": out["failed"],
                              "checks": out["checks"],
                              "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
