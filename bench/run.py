#!/usr/bin/env python3
"""The serving benchmark: one cell of ``BENCHMARK.json``, one run.

  python3 bench/run.py --workload qwen3_30b_a3b.chat --seed 7 \
      --seconds 30 --trace 0

Boots the cell's model on the chips it asks for (weights made on the
device from ``--seed``), warms up with the cell's traffic, then offers
load open loop at the cell's fixed rate for ``--seconds`` and measures.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the same window.  After the
window the server is freed and the plain reference checks a sample of
what was served.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``checks`` (each compared number beside its limit,
also the last lines of standard error).  Without a TPU, with fewer chips
than the cell asks for, or with the kernels forced to their oracles, it
exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cell as cellmod  # noqa: E402
from harness import spec  # noqa: E402

T_START = cellmod.process_start()


def main(argv=None, control=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = cellmod.run(cell, args.seed, args.seconds, bool(args.trace),
                             control=control, t_start=T_START)
    except cellmod.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    cellmod.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
