"""The on-chip benchmark: one cell of BENCHMARK.json, one run.

    python3 bench/run.py --workload granite-8b.code --seed 7 --seconds 51 --trace 0

Builds the cell's model with weights drawn from ``--seed``, warms every
shape the cell's traffic uses, serves that traffic through the engine for
``--seconds``, checks what was served against the plain float32 reference,
and prints one JSON line last on standard output.  With ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the same window.  There is no CPU
branch: without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.lib import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    try:
        result = harness.run(a.workload, a.seed, a.seconds, bool(a.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
