"""Run one cell once: set-up, the measured window, the check, the result.

``run(cell, seed, seconds, trace)`` returns the result object the command
prints as its last line; ``control="fp8"`` or ``"int8"`` checks that
control in place of the served tokens (``check.py``).
``require_chip=False`` skips the look for a TPU (the tests drive the rest
of a run on the CPU that way); the command itself never passes it.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

from harness import check, serve, spec, stats, trace as tracemod, traffic, \
    weights


# seconds the run serves on after the window, offering nothing, for the
# sampled requests to finish
DRAIN_S = 60.0


class NoChip(Exception):
    """No accelerator, too few chips, or kernels forced to the oracles."""


def process_start() -> float:
    """This process's start on the ``perf_counter`` clock (Linux
    ``/proc``); the time of this call where that cannot be read."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        age = up - ticks / os.sysconf("SC_CLK_TCK")
        return now - age if 0 <= age < 3600 else now
    except (OSError, ValueError, IndexError):
        return now


def look_for_chip(chips: int):
    import jax
    from repro.kernels.ops import IMPL_ENV
    forced = {v: os.environ[v] for v in IMPL_ENV
              if os.environ.get(v, "auto") not in ("auto", "kernel")}
    if forced:
        raise NoChip(f"kernel choice forced to {forced}; the benchmark "
                     "runs the compiled kernels only")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX finds {len(devs)}")


def compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    executable is kept, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts executables built (JAX's backend-compile event), and keeps
    every timed JAX event and every full garbage collection while
    ``watching`` is set, to name what a long tick spent its time on."""

    def __init__(self):
        import gc

        import jax
        from jax._src import dispatch
        self.n = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.events: List = []           # (end, name, seconds) while watching
        self.watching = False
        self._gc_t0 = None

        def listen(event, duration, **kw):
            if event == self._event:
                self.n += 1
            if self.watching:
                self.events.append((time.perf_counter(), event, duration))

        def collect(phase, info):
            if info.get("generation") != 2 or not self.watching:
                return
            if phase == "start":
                self._gc_t0 = time.perf_counter()
            elif self._gc_t0 is not None:
                t = time.perf_counter()
                self.events.append((t, "gc.gen2", t - self._gc_t0))
        jax.monitoring.register_event_duration_secs_listener(listen)
        gc.callbacks.append(collect)

    def long_ticks(self, walls, n: int = 5) -> List[str]:
        """The ``n`` longest ticks, each with the watched events that ended
        inside it."""
        out = []
        for t0, dur in sorted(walls, key=lambda w: -w[1])[:n]:
            inside = [f"{name} {sec:.3f} s" for end, name, sec in self.events
                      if t0 <= end <= t0 + dur]
            out.append(f"{dur:.3f} s" + (f" ({'; '.join(inside)})"
                                         if inside else ""))
        return out


@contextlib.contextmanager
def program_weights(arch, conf):
    """The program's boot builds its weights with the benchmark's
    generator (one jitted call on the device, from the seed's key)."""
    from repro.models import model as M
    orig = M.init_params
    M.init_params = weights.program_init(arch, conf)
    try:
        yield orig
    finally:
        M.init_params = orig


class RunView:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._busy = None

    percentile = staticmethod(stats.percentile)
    overlap = staticmethod(tracemod.overlap)

    def window_records(self):
        return [r for r in self.records if r.phase == "window"]

    def work(self, name: str):
        return spec.plugin("work", name)

    def trace_busy(self):
        if self._busy is None:
            dev = next(iter(self.trace["devices"].values()))
            self._busy = tracemod.busy_intervals(dev, self.trace_window)
        return self._busy

    def tick_spans(self) -> List:
        """Each tick's ``srv.tick`` span in the trace."""
        return tracemod.host_spans(self.trace, "srv.tick", self.trace_window)

    def modules_in(self, span) -> List:
        return tracemod.modules_in(self.trace, span)

    def roofline(self, kernel: str, work) -> Optional[float]:
        """Least time of the kernel's useful work in the traced ticks over
        its self time in the trace, in %; None where it never ran."""
        if self.trace is None or self.trace_window is None \
                or self.peaks is None:
            return None
        secs, n = tracemod.kernel_seconds(self.trace, kernel,
                                          self.trace_window)
        if n == 0 or secs <= 0:
            return None
        p = self.peaks
        least = sum(max(f / p.flops, b / p.hbm_bw)
                    for t in self.ticks for f, b in work.calls(
                        self.conf, t))
        return 100.0 * least / secs


def _device(jax, chips: int, traced: Optional[Dict]) -> Dict:
    devs = jax.devices()[:chips]
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs), default=0)
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak)}
    if traced is not None:
        out.update(traced)
    return out


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        control: Optional[str] = None, require_chip: bool = True,
        t_start: Optional[float] = None, log=print) -> Dict:
    t_start = process_start() if t_start is None else t_start
    import jax
    if require_chip:
        look_for_chip(cell.chips)
    log(f"compile cache: {compile_cache()}")
    counter = CompileCounter()
    dev = jax.devices()[0]
    peaks = (stats.peaks_for(dev.device_kind) if require_chip
             else stats.PEAKS.get(dev.device_kind))
    conf, mix, cp = cell.config, cell.traffic, cell.params
    serving = conf["serving"]
    mcfg = cell.arch.model_config(conf)
    from repro.core.topology import ElasticConfig
    from repro.models import model as M
    weights.check_layout(cell.arch, conf, M.init_params, mcfg)
    # a scale cell boots on its first chips and scales live to all of them
    # at the window's start; its target is compiled in set-up
    scale = cp.get("scale")
    boot_dp = int(scale["from_chips"]) if scale else cell.chips
    srv = serve.build_server(mcfg, serving, weights.fold32(seed))
    with program_weights(cell.arch, conf):
        srv.boot(ElasticConfig(dp=boot_dp, tp=1,
                               devices=tuple(range(boot_dp))))
    target = None
    if scale:
        n = int(scale["to_chips"])
        target = ElasticConfig(dp=n, tp=1, devices=tuple(range(n)))
        srv.preinitialize(target)
    t_boot = time.perf_counter()
    log(f"model: {conf['registry']} {conf['num_hidden_layers']} layers; "
        f"boot {t_boot - t_start:.2f} s after process start")

    rate, warm_s = float(cp["rate_rps"]), float(mix["warmup_s"])
    V = conf["vocab_size"]
    warm = traffic.requests(mix, rate, warm_s, V, seed, stream=0)
    win = traffic.requests(mix, rate, seconds, V, seed, stream=1,
                           first_rid=len(warm))
    ann = jax.profiler.TraceAnnotation if trace else None
    d = serve.Driver(srv, annotate=ann)
    origin = time.perf_counter()
    d.run(warm, "warmup", origin, origin + warm_s)
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        d.record_ticks = True
    compiles0 = counter.n
    walls0 = len(d.tick_walls)
    counter.watching = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with (ann("bench.window") if trace else contextlib.nullcontext()):
        if target is not None:
            d.start_scale(target)
        d.run(win, "window", t0, t0 + seconds)
    t1 = t0 + seconds
    counter.watching = False
    compiles = counter.n - compiles0
    traced_dev, breakdown, tr, tw = None, None, None, None
    if trace:
        jax.profiler.stop_trace()
        log(f"profiler stopped {time.perf_counter() - t1:.1f} s after the "
            "window closed")
    log(f"window: {len(d.window())} requests due, {compiles} compilations "
        f"inside the window; generator late p50 "
        f"{stats.percentile(d.lateness, 50)} s, max "
        f"{max(d.lateness, default=None)} s")
    log("longest ticks in the window: "
        + ", ".join(counter.long_ticks(d.tick_walls[walls0:])))

    # the sample is fixed now; serve on, offering nothing, until it is done:
    # a minute of serving, which the profiler's stop above does not shorten
    chosen = check.sample(records=d.records.values(),
                          n=int(cp["sample_requests"]), seed=seed)
    d.drain([r.req.rid for r in chosen], time.perf_counter() + DRAIN_S)
    log(f"sample: {len(chosen)} requests, the last finished "
        f"{max((r.finish or float('inf') for r in chosen), default=t1) - t1:.1f}"
        " s after the window closed")
    for r in chosen:
        if r.finish is None:
            log(f"unfinished: request {r.req.rid} due {r.due - t0:.1f} s "
                f"into the window, prompt {r.req.prompt_len}, output "
                f"{len(r.times)} of {r.req.output_len}, first token "
                + (f"{r.times[0] - r.due:.1f} s after due" if r.times
                   else "none"))
    if trace:
        t_parse = time.perf_counter()
        path = next(Path(tdir).rglob("*.xplane.pb"), None)
        if path is not None:
            tr = tracemod.load(str(path))
            tw = tracemod.window(tr)
        shutil.rmtree(tdir, ignore_errors=True)
        if tr is not None and tw is not None and tr["devices"]:
            busy = tracemod.busy_seconds(tr, tw)
            traced_dev = {"busy_s": busy, "window_s": tw[1] - tw[0]}
            breakdown = {"device_ops": tracemod.top_ops(tr, tw),
                         "idle_gaps": tracemod.idle_gaps(tr, tw)}
        log(f"trace read in {time.perf_counter() - t_parse:.1f} s")
    device = _device(jax, cell.chips, traced_dev)
    records = list(d.records.values())
    view = RunView(records=records, win=(t0, t1), seconds=seconds,
                   setup_s=setup_s, conf=conf, peaks=peaks,
                   chips=cell.chips, ticks=[t for t in d.ticks
                                            if t0 <= t.t0 < t1],
                   trace=tr if traced_dev else None, trace_window=tw,
                   busy_s=(traced_dev or {}).get("busy_s"),
                   scale=_scale_view(d) if scale else None)
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        value = spec.plugin("metrics", name).read(view)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": cell.units[name]}
        else:
            log(f"metric {name}: nothing to read in this run")

    generated = {rid: list(toks) for rid, toks in srv.engine.generated.items()}
    serve.free(srv)
    del srv, d
    t_check = time.perf_counter()
    verdict = check.run(cell, seed, records, generated, chosen,
                        control=control)
    log(f"reference check {time.perf_counter() - t_check:.1f} s")
    if scale:
        done = view.scale is not None and view.scale["phase"] == "DONE"
        verdict["checks"]["scale_not_committed"] = [int(not done), 0]
        verdict["correct"] = verdict["correct"] and done
    due = [r for r in records if r.phase == "window"]
    result = {"correct": verdict["correct"], "attempted": len(due),
              "failed": sum(1 for r in due if r.failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    return result


def _scale_view(d) -> Optional[Dict]:
    """What the scale readers see: the event's start and end on the host
    clock, the program's record of it, and the staging bytes."""
    task = d.scale_task
    if task is None:
        return None
    return {"t0": d.scale_t0, "t1": d.scale_t1,
            "phase": task.phase.name, "event": task.event,
            "stage_stats": task.stage_stats}


def emit(result: Dict) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output."""
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)

