#!/usr/bin/env python3
"""Find a cell's knee once: offered rate against what the server sustains.

  python3 bench/sweep.py --workload qwen3_30b_a3b.chat --seed 5 \
      --seconds 20 --rates 4 6 8 10 12

One process boots the cell's server once, then for each rate offers the
cell's traffic open loop for ``--seconds`` (after a warm-up at that rate),
and drains what is left before the next rate.  Per rate it prints the
output tokens/s, the p50/p95 time to first token of the requests due in
the window, the p95 gap between tokens, and the queue left when the
window closed.  The knee is the
highest rate whose queue does not grow through the window; a cell runs at
about four fifths of it.  Not a benchmark run: it prints no result line.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cell as cellmod  # noqa: E402
from harness import serve, spec, stats, traffic, weights  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cellmod.look_for_chip(cell.chips)
    cellmod.compile_cache()
    from repro.core.topology import ElasticConfig
    conf, mix = cell.config, cell.traffic
    srv = serve.build_server(cell.arch.model_config(conf), conf["serving"],
                             weights.fold32(args.seed))
    with cellmod.program_weights(cell.arch, conf):
        srv.boot(ElasticConfig(dp=cell.chips, tp=1,
                               devices=tuple(range(cell.chips))))
    d = serve.Driver(srv)
    rid = 0
    for k, rate in enumerate(args.rates):
        warm = traffic.requests(mix, rate, mix["warmup_s"],
                                conf["vocab_size"], args.seed, 10 + 2 * k,
                                first_rid=rid)
        rid += len(warm)
        win = traffic.requests(mix, rate, args.seconds, conf["vocab_size"],
                               args.seed, 11 + 2 * k, first_rid=rid)
        rid += len(win)
        o = time.perf_counter()
        d.run(warm, f"warm{k}", o, o + mix["warmup_s"])
        t0 = time.perf_counter()
        d.run(win, f"rate{k}", t0, t0 + args.seconds)
        t1 = t0 + args.seconds
        queued = len(srv.queue)
        toks = sum(1 for r in d.records.values() for t in r.times
                   if t0 <= t < t1)
        due = [r for r in d.records.values() if r.phase == f"rate{k}"]
        ttft = [min(r.times[0] if r.times else t1, t1) - r.due for r in due]
        itl = [b - a for r in d.records.values()
               for a, b in zip(r.times, r.times[1:]) if t0 <= a and b < t1]
        print(f"rate {rate:g}/s: output {toks / args.seconds:.1f} tokens/s, "
              f"ttft p50 {stats.percentile(ttft, 50):.3f} s p95 "
              f"{stats.percentile(ttft, 95):.3f} s, itl p95 "
              f"{stats.percentile(itl, 95)} s, queue at close "
              f"{queued}, running {srv.engine.active_count()}", flush=True)
        if k == len(args.rates) - 1:
            break
        while srv.queue or srv.engine.active_count():
            d.tick(time.perf_counter())
    return 0


if __name__ == "__main__":
    sys.exit(main())
