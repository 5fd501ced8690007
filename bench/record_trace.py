#!/usr/bin/env python3
"""Record a few ticks of a cell's traced window as test data.

  python3 bench/record_trace.py --workload qwen3_30b_a3b.chat --seed 7 \
      --seconds 51 --ticks 3 --out bench/testdata/qwen3_chat_v5e_spans.json

Runs the cell once as ``bench/run.py --trace 1`` does and prints its result
line.  Where the harness reads the profiler trace, this keeps, in the form
``harness.trace.load`` gives, the first ``--ticks`` consecutive
``srv.tick`` spans from the middle of the window on of which one holds a
prompt's final chunk (``srv.prefill.read``): the device ops and executable
runs that start inside them, the host annotations that lie inside them,
and a ``bench.window`` annotation around them.  ``--window-out`` also keeps
the whole window's host annotations and executable runs.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cell as cellmod  # noqa: E402
from harness import spec  # noqa: E402
from harness import trace as tracemod  # noqa: E402

T_START = cellmod.process_start()


def cut(tr, n_ticks: int):
    """The first ``n_ticks`` ticks from the window's middle on that hold a
    final chunk, as a trace of their own; None where there are none."""
    w = tracemod.window(tr)
    ticks = tracemod.host_spans(tr, "srv.tick", w)
    reads = [s for n, s, _ in tr["host"] if n == "srv.prefill.read"]
    mid = (w[0] + w[1]) / 2
    for i, (t0, _) in enumerate(ticks):
        run = ticks[i:i + n_ticks]
        if t0 < mid or len(run) < n_ticks:
            continue
        a, b = run[0][0], run[-1][1]
        if not any(a <= s < b for s in reads):
            continue

        def inside(evs):
            return [e for e in evs if a <= e[1] < b]
        devs = {name: {"ops": inside(d["ops"]),
                       "modules": inside(d["modules"])}
                for name, d in tr["devices"].items()}
        host = [h for h in tr["host"] if h[0] != "bench.window"
                and a <= h[1] and h[1] + h[2] <= b]
        return {"devices": devs, "host": host + [["bench.window", a, b - a]]}
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--ticks", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--window-out", default=None)
    args = ap.parse_args(argv)
    load = tracemod.load

    def load_and_keep(path):
        tr = load(path)
        w = tracemod.window(tr)
        if w is not None:
            piece = cut(tr, args.ticks)
            if piece is not None:
                Path(args.out).write_text(json.dumps(piece))
            if args.window_out:
                mods = tracemod.modules_in(tr, w)
                host = [h for h in tr["host"]
                        if w[0] <= h[1] and h[1] + h[2] <= w[1]]
                Path(args.window_out).write_text(
                    json.dumps({"host": host, "modules": mods}))
        return tr

    tracemod.load = load_and_keep
    cell = spec.load_cell(args.workload)
    try:
        result = cellmod.run(cell, args.seed, args.seconds, True,
                             t_start=T_START)
    except cellmod.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    cellmod.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
