"""The benchmark harness: finds a cell's parts by name, runs one window.

Every part is found by the name ``BENCHMARK.json`` gives it, so a later
change adds a configuration, a traffic mix or a metric by adding files:

* configuration ``<c>``: the file its entry names (sizes, limits), whose
  ``"model"`` names the family module beside it,
  ``bench/configs/<model>.py`` (builds the system under test from the seed,
  and holds the plain reference);
* traffic ``<t>``: ``bench/traffic/<t>.json``, read by ``generator.py``;
* metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(run)`` returns a
  number, or None where the run holds nothing to read.

One run: set-up (weights on the device, every shape the cell uses warmed),
then a window of ``seconds`` driven by the traffic, then a drain of the
requests due in the window (up to ``DRAIN_S``), then the comparison with
the reference. Latency runs from each request's due time.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from bench import devtrace, generator, yardstick

ROOT = Path(__file__).resolve().parents[1]
DRAIN_S = 60.0
TRACE_SLICE_S = 3.0
OUT_DIR = "bench_out"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ finding
def load_module(path: Path):
    name = "bench_part_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    entry: dict
    config: dict            # the configuration file's contents
    model: object           # its family module
    mix: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, workload: str) -> Cell:
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    cfg_path = root / conf["file"]
    cfg = json.loads(cfg_path.read_text())
    model = load_module(cfg_path.with_name(cfg["model"] + ".py"))
    mix = generator.load_mix(root / "bench" / "traffic"
                             / (entry["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in reported and _applies(m, workload)]
    return Cell(workload, entry, cfg, model, mix, e2e, per_layer)


def metric_reader(root: Path, name: str):
    return load_module(Path(root) / "bench" / "metrics" / (name + ".py"))


# ------------------------------------------------------------------- device
def require_chip(chips: int):
    """The TPU devices and their peaks; no accelerator, too few chips or a
    device kind missing from the peaks table is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs, yardstick.load_peaks(devs[0].device_kind)


class CompileCounter:
    """Backend compiles and persistent-cache misses, from JAX's events."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.misses = 0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def host_counters() -> dict:
    """What the host did to this process: its CPU seconds, its context
    switches and major faults, its threads, and the machine's steal time
    (seconds another tenant of the hypervisor held its CPUs)."""
    import os
    import resource
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"cpu_s": ru.ru_utime + ru.ru_stime, "ctx_invol": ru.ru_nivcsw,
           "ctx_vol": ru.ru_nvcsw, "major_faults": ru.ru_majflt,
           "threads": len(os.listdir("/proc/self/task"))}
    with contextlib.suppress(OSError, IndexError, ValueError):
        fields = Path("/proc/stat").read_text().split("\n", 1)[0].split()
        out["steal_s"] = int(fields[8]) / os.sysconf("SC_CLK_TCK")
    return out


class GcTimer:
    """Python's garbage collections while it is entered: how many, and the
    longest as (generation, seconds)."""

    def __init__(self):
        self.n, self.longest, self._t0 = 0, (0, 0.0), 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        took = time.perf_counter() - self._t0
        self.n += 1
        if took > self.longest[1]:
            self.longest = (info["generation"], took)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)


def stalls(run: Run, sched: generator.Schedule, top: int = 5) -> list:
    """The longest host stalls of the window, [(when, seconds)]: the
    generator's lag in an open loop, a call's time past the median in a
    closed one."""
    if sched.loop == "open":
        found = [(r.due, lag) for r, lag in zip(run.reqs, run.lag)]
    else:
        took = sorted(s[2] - s[1] for s in run.spans) or [0.0]
        med = took[len(took) // 2]
        found = [(s[1], s[2] - s[1] - med) for s in run.spans]
    return sorted(found, key=lambda f: -f[1])[:top]


def device_info(devs, n_used: int) -> dict:
    peak = 0
    for d in devs[:n_used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ------------------------------------------------------------------ driving
@dataclass
class Req:
    due: float
    t_submit: float | None = None
    t_admit: float | None = None
    out_times: list = field(default_factory=list)
    t_done: float | None = None
    value: object = None


@dataclass
class Run:
    """What a window left behind, for the metric readers and the check."""
    seconds: float
    reqs: list = field(default_factory=list)
    spans: list = field(default_factory=list)     # (name, t0, t1, items)
    calls: int = 0
    items_per_call: int = 0
    items_in_window: int = 0
    trace: dict | None = None
    traced: tuple = (0.0, 0.0)     # the traced slice, window seconds
    peaks: dict | None = None
    config: dict | None = None
    counters: dict = field(default_factory=dict)
    lag: list = field(default_factory=list)

    @property
    def steady_end(self) -> float:
        """Where the window's undisturbed part ends: at the profiler's start
        in a traced run (starting it stalls the host), else at the close."""
        return self.traced[0] if self.traced[1] > 0 else self.seconds

    def spans_named(self, name: str) -> list:
        """The spans of ``name`` that ended in the undisturbed window."""
        return [s for s in self.spans if s[0] == name
                and s[2] <= self.steady_end]

    def steady_reqs(self) -> list:
        """The requests due in the undisturbed window."""
        return [r for r in self.reqs if r.due < self.steady_end]


def _start_profiler(out: Path) -> None:
    """JAX's profiler recording device operations and the host's level-1
    annotations (the benchmark's spans) only: its Python tracer and the
    runtime's own host events slow the traced host path, which the device
    then shows as idle time."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)


class _Tracer:
    """Profiler over the last ``TRACE_SLICE_S`` of the window, with the
    benchmark's host spans written into the same trace."""

    def __init__(self, out: Path | None, seconds: float):
        self.out = out
        self.start_at = max(0.0, seconds - TRACE_SLICE_S)
        self.on = False
        self._window = None
        self.t_on = self.t_off = 0.0

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(devtrace.SPAN_PREFIX + name)

    def poll(self, now: float) -> None:
        if self.out is None or self.on or now < self.start_at:
            return
        import jax
        _start_profiler(self.out)
        self._window = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
        self._window.__enter__()
        self.on = True
        self.t_on = now

    def close_window(self, now: float = 0.0) -> None:
        if self._window is not None:
            self.t_off = now
            self._window.__exit__(None, None, None)
            self._window = None

    def prime(self) -> None:
        """Start and stop the profiler once in set-up: its first start pays
        a one-time initialisation (seconds, on a TPU host) that would
        otherwise land inside the window."""
        if self.out is None:
            return
        import jax
        _start_profiler(self.out)
        jax.profiler.stop_trace()
        shutil.rmtree(self.out, ignore_errors=True)

    def stop(self) -> dict | None:
        if self.out is None:
            return None
        self.close_window()
        if not self.on:
            return None
        import jax
        jax.profiler.stop_trace()
        self.on = False
        return devtrace.extract(devtrace.newest_xplane(self.out))


def new_run(sched: generator.Schedule, seconds: float) -> Run:
    """The window's record, with one entry per request due in it; made in
    set-up so that the window allocates none of it."""
    run = Run(seconds, items_per_call=sched.batch)
    run.reqs = [Req(float(d)) for d in sched.due]
    return run


def drive_open(system, sched: generator.Schedule, seconds: float, *,
               clock=time.perf_counter, sleep=time.sleep,
               tracer: _Tracer | None = None, run: Run | None = None) -> Run:
    """Offer the schedule as an open loop; stamp every request's answer.

    ``system.submit(i)`` queues request ``i``; ``system.step()`` runs one
    engine step and returns ``[(i, kind, value)]`` with kind ``"out"`` (a
    part of a streamed answer, such as a token) or ``"done"`` (the answer);
    ``system.active`` says whether anything is queued or in flight;
    ``system.admissions()`` gives ``{i: t_admit}`` on the same clock.
    """
    tracer = tracer or _Tracer(None, seconds)
    run = run or new_run(sched, seconds)
    n, nxt = sched.n, 0
    t0 = clock()
    while True:
        now = clock() - t0
        tracer.poll(now)
        if now >= seconds:
            tracer.close_window(now)
        while nxt < n and run.reqs[nxt].due <= now:
            with tracer.span("submit"):
                system.submit(nxt)
            run.reqs[nxt].t_submit = now
            run.lag.append(now - run.reqs[nxt].due)
            nxt += 1
        if system.active:
            with tracer.span("step"):
                ts = clock() - t0
                events = system.step()
                te = clock() - t0
            run.spans.append(("step", ts, te, system.last_step_items))
            for i, kind, value in events:
                r = run.reqs[i]
                r.out_times.append(te)
                if kind == "done":
                    r.t_done, r.value = te, value
        elif nxt >= n:
            break
        else:
            wait = run.reqs[nxt].due - (clock() - t0)
            if wait > 0:
                with tracer.span("sleep"):
                    sleep(max(wait, 1e-6))
        if now > seconds + DRAIN_S:
            break
    tracer.close_window(min(clock() - t0, seconds))
    run.traced = (tracer.t_on, tracer.t_off)
    run.trace = tracer.stop()
    for i, t_admit in system.admissions().items():
        run.reqs[i].t_admit = t_admit - t0
    return run


def drive_closed(system, sched: generator.Schedule, seconds: float, *,
                 clock=time.perf_counter,
                 tracer: _Tracer | None = None, run: Run | None = None) -> Run:
    """One caller in a closed loop: the next call goes when the last one
    returns; calls that return inside the window count."""
    tracer = tracer or _Tracer(None, seconds)
    run = run or new_run(sched, seconds)
    t0 = clock()
    while True:
        now = clock() - t0
        tracer.poll(now)
        if now >= seconds:
            tracer.close_window(now)
            break
        with tracer.span("call"):
            ts = clock() - t0
            system.call(run.calls)
            te = clock() - t0
        run.spans.append(("call", ts, te, sched.batch))
        run.calls += 1
        if te <= seconds:
            run.items_in_window += sched.batch
    run.traced = (tracer.t_on, tracer.t_off)
    run.trace = tracer.stop()
    return run


# -------------------------------------------------------------------- a run
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, t_start: float | None = None,
             need_chip: bool = True, fault=None, rate_hz: float | None = None,
             keep: dict | None = None) -> dict:
    """One run of one cell; returns the result line's object.

    ``need_chip=False`` skips the look for a chip (tests run the rest of a
    run on the CPU). ``fault`` is handed to the system, which breaks its
    timed path with it: a planted fault for the tests, or ``"control"``,
    the plain reference at the next lower precision put in the program's
    place (``calibrate.py``, the tests); the run's comparison then has to
    come out not correct. ``rate_hz`` replaces an open loop's rate (the
    knee sweep); ``keep`` receives the window's ``Run`` under ``"run"``.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = find_cell(root, workload)
    if rate_hz is not None:
        cell.mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"],
                                                rate_hz=rate_hz))
    chips = int(cell.entry["chips"])
    if need_chip:
        devs, peaks = require_chip(chips)
        from repro.launch import compile_cache
        cache_dir = compile_cache.enable()
    else:
        import jax
        devs = jax.devices()
        peaks = yardstick.load_peaks("TPU v5 lite")
        cache_dir = None
    log(f"device: platform {devs[0].platform}, device_kind "
        f"{devs[0].device_kind!r}, {len(devs)} device(s), cell needs "
        f"{chips}; compile cache {cache_dir}")
    counter = CompileCounter()
    sched = generator.schedule(cell.mix, seed, seconds)
    system = cell.model.build(cell.config, cell.mix, sched, seed,
                              fault=fault)
    system.warmup()
    out = Path(root) / OUT_DIR / workload / f"seed-{seed}"
    tracer = None
    if trace:
        shutil.rmtree(out / "trace", ignore_errors=True)
        tracer = _Tracer(out / "trace", seconds)
        tracer.prime()
    run = new_run(sched, seconds)
    # what set-up allocated is kept for the whole run: move it out of the
    # collector's reach, so a full collection of it cannot stall the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    compiles0, misses0 = counter.compiles, counter.misses
    log(f"setup: {setup_s:.3f} s; {counter.compiles} compiles, "
        f"{counter.hits} cache hits, {counter.misses} misses so far")

    drive = drive_open if sched.loop == "open" else drive_closed
    host0 = host_counters()
    with GcTimer() as gct:
        drive(system, sched, seconds, tracer=tracer, run=run)
    host1 = host_counters()
    in_window = counter.compiles - compiles0
    log(f"window: {seconds} s, {in_window} compiles and "
        f"{counter.misses - misses0} cache misses inside it")
    log("host in the window: " + ", ".join(
        f"{k} {host1[k] - host0[k]:.6g}" for k in host0
        if k in host1 and k != "threads")
        + f"; {host1['threads']} threads")
    log(f"gc in the window: {gct.n} collections, the longest of "
        f"generation {gct.longest[0]} for {gct.longest[1]:.6f} s")
    log("longest stalls (at s, for s): " + ", ".join(
        f"{t:.3f} {d:.4f}" for t, d in stalls(run, sched)))
    run.peaks, run.config = peaks, cell.config
    if keep is not None:
        keep["run"] = run
    run.counters = system.counters()
    if run.trace is not None:
        events = run.trace
        out.mkdir(parents=True, exist_ok=True)
        devtrace.save(events, out / "trace_events.json")
        shutil.rmtree(out / "trace", ignore_errors=True)
        # a CPU run (the tests) has no device plane to reduce
        run.trace = (devtrace.reduce(events, system.kernel_families())
                     if events["device"] else None)

    device = device_info(devs, chips)
    if sched.loop == "open":
        attempted = len(run.reqs)
        failed = sum(1 for r in run.reqs if r.t_done is None)
    else:
        attempted = run.calls * run.items_per_call
        failed = 0
    if sched.loop == "open":
        lag = sorted(run.lag) or [0.0]
        log(f"requests: {attempted} attempted, {failed} failed; generator "
            f"lag p50 {lag[len(lag) // 2] * 1e3:.3f} ms, max "
            f"{lag[-1] * 1e3:.3f} ms")
    else:
        took = sorted(s[2] - s[1] for s in run.spans) or [0.0]
        log(f"calls: {run.calls}, {attempted} items; call time p50 "
            f"{took[len(took) // 2] * 1e3:.3f} ms, max "
            f"{took[-1] * 1e3:.3f} ms")
    system.release()

    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = metric_reader(root, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for k, v in sorted(run.counters.items()):
        if not isinstance(v, (list, dict)):
            log(f"counter {k}: {v}")

    checks = system.check(run)
    correct = bool(checks) and all(c["value"] <= c["limit"] for c in checks)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})")
    return result
