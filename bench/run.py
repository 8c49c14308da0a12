#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload rgg-mesh.offline --seed 7 --seconds 20 --trace 0

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  ``--trace 0`` measures the
cell's end-to-end metrics over ``--seconds``; ``--trace 1`` runs the mix's
fixed traced window under the JAX profiler and reports its per-layer
metrics.  The last line of stdout is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit);
the numbers compared are also the last lines of stderr.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, or when the checkout does not hold the program.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    missing = harness.environment_ok(ROOT)
    if missing:
        print(f"bench: {missing}", file=sys.stderr)
        return 2
    harness.enable_cache()
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START, root=ROOT)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
