#!/usr/bin/env python
"""Render a Chrome-trace JSON (exported by repro.obs) as text reports.

Usage:  python tools/trace_report.py trace.json [--cat CAT] [--timeline N]

Three sections:

* **summary** — per (cat, name) over complete ("X") spans: count, total /
  mean / max duration in ms, sorted by total time descending;
* **phase timeline** — scale-phase spans (cat ``scale``) and HMM staging
  spans in start order with text bars, the at-a-glance view of the
  STAGING ∥ COMPILING ∥ MIGRATING concurrency claim;
* **overlap** — how many ``transfer`` spans overlapped a ``srv.step``
  span (one engine tick's prefill and decode) in wall-clock (the paper's
  serving-while-staging evidence).

Stdlib only; works on traces from the real engine (perf_counter domain)
and the simulator (sim-time domain) alike.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

BAR_WIDTH = 48


def _spans(doc, cat=None):
    for rec in doc.get("traceEvents", []):
        if rec.get("ph") != "X":
            continue
        if cat is not None and rec.get("cat") != cat:
            continue
        yield rec


def summary_rows(doc, cat=None):
    agg = defaultdict(lambda: [0, 0.0, 0.0])  # count, total_us, max_us
    for rec in _spans(doc, cat):
        key = (rec.get("cat", ""), rec["name"])
        a = agg[key]
        a[0] += 1
        a[1] += rec["dur"]
        a[2] = max(a[2], rec["dur"])
    rows = [(c, n, cnt, tot / 1e3, tot / cnt / 1e3, mx / 1e3)
            for (c, n), (cnt, tot, mx) in agg.items()]
    rows.sort(key=lambda r: -r[3])
    return rows


def print_summary(doc, cat=None, file=sys.stdout):
    rows = summary_rows(doc, cat)
    print("\n## span summary", file=file)
    hdr = ("cat", "name", "count", "total_ms", "mean_ms", "max_ms")
    fmt = [str, str, str,
           lambda v: f"{v:.2f}", lambda v: f"{v:.3f}", lambda v: f"{v:.3f}"]
    cells = [hdr] + [tuple(f(v) for f, v in zip(fmt, r)) for r in rows]
    widths = [max(len(c[i]) for c in cells) for i in range(len(hdr))]
    for c in cells:
        print("  ".join(v.ljust(w) for v, w in zip(c, widths)), file=file)
    return rows


def print_timeline(doc, max_rows=40, file=sys.stdout):
    spans = sorted((r for r in _spans(doc)
                    if r.get("cat") in ("scale", "hmm")),
                   key=lambda r: r["ts"])[:max_rows]
    print("\n## phase timeline (scale + hmm spans)", file=file)
    if not spans:
        print("(no scale/hmm spans in trace)", file=file)
        return
    t0 = min(r["ts"] for r in spans)
    t1 = max(r["ts"] + r["dur"] for r in spans)
    scale = BAR_WIDTH / max(t1 - t0, 1e-9)
    for r in spans:
        a = int((r["ts"] - t0) * scale)
        b = max(int((r["ts"] + r["dur"] - t0) * scale), a + 1)
        bar = " " * a + "#" * (b - a)
        print(f"{r['name']:<22} {bar:<{BAR_WIDTH}} "
              f"[{(r['ts'] - t0) / 1e3:9.2f}ms +{r['dur'] / 1e3:8.2f}ms]",
              file=file)


def overlap_report(doc):
    """(n_transfer, n_overlapping, decode_ticks) — a transfer span counts
    as overlapping when any srv.step span intersects it in time."""
    transfers = list(_spans(doc, "transfer"))
    ticks = [r for r in _spans(doc, "serve") if r["name"] == "srv.step"]
    n_overlap = 0
    for tr in transfers:
        a0, a1 = tr["ts"], tr["ts"] + tr["dur"]
        if any(t["ts"] < a1 and a0 < t["ts"] + t["dur"] for t in ticks):
            n_overlap += 1
    return len(transfers), n_overlap, len(ticks)


def print_overlap(doc, file=sys.stdout):
    n_tr, n_ov, n_ticks = overlap_report(doc)
    print("\n## staging/serving overlap", file=file)
    print(f"transfer spans: {n_tr}  decode ticks: {n_ticks}  "
          f"transfer spans overlapping a decode tick: {n_ov}", file=file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome-trace JSON from repro.obs")
    ap.add_argument("--cat", default=None,
                    help="restrict the summary to one category")
    ap.add_argument("--timeline", type=int, default=40, metavar="N",
                    help="max spans in the phase timeline (default 40)")
    args = ap.parse_args(argv)
    with open(args.trace) as fh:
        doc = json.load(fh)
    n = len(doc.get("traceEvents", []))
    print(f"# trace report: {args.trace} ({n} events)")
    print_summary(doc, args.cat)
    print_timeline(doc, args.timeline)
    print_overlap(doc)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
