"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a small plain form:
for each device plane its ``XLA Ops`` and ``XLA Modules`` events, and the
host annotations the harness wrote (``bench.*`` and ``srv.tick``), each as
``[name, start_s, dur_s]`` on the profiler's one clock.  Every reduction
below works on that form, which is also what ``testdata/`` holds.

* busy time: the union of a device's op intervals inside the window;
* a kernel's time: the self time of the ops whose name starts with the
  kernel's name (time covered by an op nested inside it on the same line
  is the nested op's);
* idle gaps: the holes in that union, each named by the innermost host
  annotation that holds its midpoint.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

HOST_PREFIXES = ("bench.", "srv.")
Interval = Tuple[float, float]


def op_name(name: str) -> str:
    """The HLO instruction's name from a TPU op event, whose name is the
    instruction's whole text (``%paged_gmm.23 = bf16[...] custom-call(...``
    becomes ``paged_gmm.23``)."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def load(path: str) -> Dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    name = op_name if key == "ops" else str
                    dev[key].extend([name(e.name), e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9]
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIXES))
    return out


def window(tr: Dict, name: str = "bench.window") -> Optional[Interval]:
    spans = [(s, s + d) for n, s, d in tr["host"] if n == name]
    return spans[0] if spans else None


def _clip(s: float, e: float, w: Interval) -> Optional[Interval]:
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(dev: Dict, w: Interval) -> List[Interval]:
    return union(c for n, s, d in dev["ops"]
                 if (c := _clip(s, s + d, w)) is not None)


def busy_seconds(tr: Dict, w: Interval) -> float:
    """Busy seconds in ``w``, averaged over the device planes."""
    devs = list(tr["devices"].values())
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(d, w))
               for d in devs) / len(devs)


def self_times(ops: List) -> List[Tuple[str, float, float]]:
    """``[name, start, self_seconds]`` per op: its duration less what ops
    nested inside it on the same line cover."""
    evs = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    stack: List[List] = []        # [name, start, end, child seconds]
    for n, s, d in evs:
        while stack and s >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[1], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += min(d, stack[-1][2] - s)
        stack.append([n, s, s + d, 0.0])
    out.extend((t[0], t[1], t[2] - t[1] - t[3]) for t in stack)
    return out


def _in(w: Interval, start: float) -> bool:
    return w[0] <= start < w[1]


def kernel_seconds(tr: Dict, prefix: str, w: Interval) -> Tuple[float, int]:
    """Self seconds and count of the ops named ``prefix`` or
    ``prefix.<n>`` that start in ``w``, summed over the devices."""
    pat = re.compile(re.escape(prefix) + r"(\.\d+)?$")
    total, n = 0.0, 0
    for dev in tr["devices"].values():
        for name, s, d in self_times(dev["ops"]):
            if _in(w, s) and pat.match(name):
                total += d
                n += 1
    return total, n


def modules_in(tr: Dict, w: Interval) -> List:
    """The executable runs (``XLA Modules`` line: one event per call of a
    compiled program) that start in ``w``, in order."""
    return sorted((e for dev in tr["devices"].values()
                   for e in dev["modules"] if _in(w, e[1])),
                  key=lambda e: e[1])


def host_spans(tr: Dict, name: str, w: Interval) -> List[Interval]:
    return sorted((s, s + d) for n, s, d in tr["host"]
                  if n == name and w[0] <= s and s + d <= w[1])


def gaps(tr: Dict, w: Interval) -> List[Interval]:
    out = []
    for dev in tr["devices"].values():
        edge = w[0]
        for s, e in busy_intervals(dev, w):
            if s > edge:
                out.append((edge, s))
            edge = max(edge, e)
        if w[1] > edge:
            out.append((edge, w[1]))
    return out


def host_name(tr: Dict, t: float, skip: str = "bench.window") -> str:
    """The innermost harness annotation open at host time ``t``."""
    best = None
    for n, s, d in tr["host"]:
        if n != skip and s <= t < s + d and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else "outside_annotations"


def overlap(spans: List[Interval], s: float, e: float) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in spans)


def top_ops(tr: Dict, w: Interval, n: int = 10) -> List[List]:
    """The ``n`` op names (numeric suffix dropped) with the most self time
    in ``w``, summed over the devices."""
    acc: Dict[str, float] = {}
    for dev in tr["devices"].values():
        for name, s, d in self_times(dev["ops"]):
            if _in(w, s):
                key = re.sub(r"\.\d+$", "", name)
                acc[key] = acc.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Dict, w: Interval, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps, each named by what the host was doing
    at its midpoint."""
    gs = sorted(gaps(tr, w), key=lambda g: g[0] - g[1])[:n]
    return [[host_name(tr, (a + b) / 2), b - a] for a, b in gs]
