"""Break one benchmark cell's time down by the program's own spans.

    python tools/span_report.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> --recorder <0|1> [--out <dir>]

Runs the cell through the benchmark harness exactly as ``bench/run.py``
does (``bench/harness.py::run_cell``), with the BCNN engine's span log
(``serve/slots.py::SpanLog``) turned on over the window when
``--recorder 1``. It prints one JSON object as the last line: the run's
result as ``bench/run.py`` prints it, ``engine_step_ms`` (the harness's
reading of its own step span), ``bulk_input_counts`` (the bulk forward's
calls by the route their batch took, ``BCNNEngine.batch_input_counts``;
empty for an engine with no bulk path), and under ``"spans"``:

* ``host_ms`` / ``wait_ms``: the medians, over the engine's ``engine.step``
  (or ``engine.classify_batch``) spans that ended in the undisturbed
  window, of the span less its ``engine.wait`` (``bulk.wait``) child, and
  of that child; ``parts_ms``, the median of each child span;
* ``stalls``: the harness's longest stalls, each with the innermost
  program span it fell in.

In a traced run, with the recorder on or off, ``"trace"`` holds:
``idle_gaps``, the longest device idle gaps, each labelled by the innermost
program (``repro.``) or benchmark (``bench.``) host span covering its
middle; ``idle_by_span``, all idle time summed by that label; ``layer_s``,
device seconds per layer scope (``core/bcnn.py::group_scope``, or for
Bi-Real Net ``bireal/stem``, ``bireal/stage<s>/block<b>[/down]`` and
``bireal/head``, read from the operation's metadata in the trace),
operations outside every scope put down to their source line;
``stage_s``, the Bi-Real scopes summed by stage; and ``device_ops``, the
longest operations with their scope.

With ``--out`` the report and a sample of the device events' metadata go
to ``<out>/<cell>-seed-<n>-trace-<t>-rec-<r>.json``, and a traced run's
profile beside it, gzipped (``.xplane.pb.gz``).

This reads what the harness leaves in its ``Run`` and device trace without
changing how the harness measures: the span log is turned on just before
the window opens, and the device trace is read once more before the
harness reduces it.
"""
from __future__ import annotations

import argparse
import gzip
import json
import re
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

LAYER = re.compile(r"conv\d(_\d)?|fc\d")
BIREAL = re.compile(r"stem|head|stage\d+|block\d+|down")
WAIT_CHILD = {"engine.step": "engine.wait",
              "engine.classify_batch": "bulk.wait"}
SAMPLE = 40         # device events kept with all their stats, to read by hand


# --------------------------------------------------------------- the spans
def median_ms(values):
    return statistics.median(values) * 1e3 if values else None


def window_spans(spans, t0: float, steady_end: float) -> list:
    """The log's spans on the window's clock (seconds from ``t0``) that
    ended in the undisturbed window, [0, ``steady_end``]."""
    out = []
    for s in spans:
        s = s._replace(t0=s.t0 - t0, t1=s.t1 - t0)
        if 0.0 <= s.t0 and s.t1 <= steady_end:
            out.append(s)
    return out


def host_and_wait(spans, top: str) -> dict:
    """Medians of each ``top`` span less its wait child, of that child, and
    of each kind of child; over the ``top`` spans that have a wait."""
    wait = WAIT_CHILD[top]
    kids: dict[int, dict[str, float]] = {}
    for s in spans:
        kids.setdefault(s.parent, {})
        kids[s.parent][s.name] = kids[s.parent].get(s.name, 0.0) \
            + s.t1 - s.t0
    host, waited, parts = [], [], {}
    for s in spans:
        if s.name != top or wait not in kids.get(s.id, {}):
            continue
        k = kids[s.id]
        host.append(s.t1 - s.t0 - k[wait])
        waited.append(k[wait])
        for name, d in k.items():
            parts.setdefault(name, []).append(d)
    return {"top": top, "n": len(host), "host_ms": median_ms(host),
            "wait_ms": median_ms(waited),
            "parts_ms": {n: median_ms(v) for n, v in sorted(parts.items())}}


def stall_spans(stalls, spans, intervals=None) -> list:
    """[(at, seconds, span, overlap)]: each stall with the innermost program
    span it fell in. From the top level down, the span that overlaps the
    stall's interval most is taken, then its child that does, until no
    child overlaps; ``overlap`` is the last one's share of the interval, in
    seconds. ``intervals`` gives each stall's interval; by default
    ``(at, at + seconds)``."""
    kids: dict[int, list] = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    out = []
    for k, (at, d) in enumerate(stalls):
        lo, hi = intervals[k] if intervals else (at, at + d)
        name, ov, parent = "none", 0.0, -1
        while True:
            best = max(((min(hi, s.t1) - max(lo, s.t0), s)
                        for s in kids.get(parent, ())),
                       key=lambda o: o[0], default=(0.0, None))
            if best[0] <= 0:
                break
            ov, name, parent = best[0], best[1].name, best[1].id
        out.append([at, d, name, ov])
    return out


# ------------------------------------------------------- the device trace
def scope_of(stats: dict) -> str:
    """The layer scope in an operation's framework name (``tf_op``, such as
    ``jit(fwd)/conv2/jit(xnor_conv2d)/reduce_sum``, or
    ``jit(fwd)/bireal/stage2/block1/down/dot_general``, whose scope is
    ``bireal/stage2/block1/down``); outside every scope, the source line it
    came from (``source:<file>:<line>``); else "none"."""
    parts = str(stats.get("tf_op", "")).split("/")
    if "bireal" in parts:
        i = j = parts.index("bireal") + 1
        while j < len(parts) and BIREAL.fullmatch(parts[j]):
            j += 1
        return "/".join(parts[i - 1:j])
    for part in parts:
        if LAYER.fullmatch(part):
            return part
    src = stats.get("source")
    return "source:" + src.rsplit("/", 1)[-1] if src else "none"


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def op_metadata(xspace: bytes) -> dict:
    """{plane: {op: {stat: value}}}: for each TPU plane, the string stats
    that a profile keeps on each operation's metadata (the operation's
    framework name among them), which ``jax.profiler.ProfileData`` does
    not expose. Read from
    the serialized ``XSpace`` (tsl/profiler/protobuf/xplane.proto): its
    planes (field 1), each with a name (2), event metadata (4: id → name 2,
    display name 4, stats 5) and stat metadata (5: id → name 2); a stat
    holds its metadata id (1) and a string (5) or a reference to a stat
    metadata's name (7)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names, events = {}, []
        for k, v in fields:
            if k not in (4, 5):
                continue
            value = dict(_fields(v)).get(2, b"")      # the map entry's value
            if k == 5:
                meta = dict(_fields(value))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
            else:
                events.append(list(_fields(value)))
        ops = {}
        for meta in events:
            stats = {}
            for k, v in meta:
                if k == 5:
                    st = dict(_fields(v))
                    stats[st.get(1, 0)] = (bytes(st[5]).decode() if 5 in st
                                           else st.get(7))
            stats = {stat_names.get(sid, str(sid)):
                     stat_names.get(v, "") if isinstance(v, int) else v
                     for sid, v in stats.items() if v is not None}
            for k, v in meta:
                if k in (2, 4):
                    ops[bytes(v).decode()] = stats
        out[name] = ops
    return out


def extract(xplane) -> dict:
    """{"device": {plane: [[op, start_ns, dur_ns, scope], ...]},
    "host": [[span, start_ns, dur_ns], ...], "sample": [...]}: the device
    operations with their scope, the program's and the benchmark's host
    spans, and the first ``SAMPLE`` device events with their stats."""
    import jax
    from bench import devtrace
    from repro.serve.slots import TRACE_PREFIX
    raw = Path(xplane).read_bytes()
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    metadata = op_metadata(raw)
    device, host, seen = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            meta = metadata.get(plane.name, {})
            for line in plane.lines:
                if line.name != devtrace.DEVICE_LINE:
                    continue
                for e in line.events:
                    stats = {k: v for k, v in e.stats}
                    stats.update(meta.get(e.name, {}))
                    if len(seen) < SAMPLE:
                        seen.append({"name": e.name[:400], "stats": {
                            k: str(v)[:400] for k, v in stats.items()}})
                    ops.append([devtrace.op_name(e.name), int(e.start_ns),
                                int(e.duration_ns), scope_of(stats)])
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((TRACE_PREFIX,
                                          devtrace.SPAN_PREFIX)):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host, "sample": seen}


def reduce(events: dict, top: int = 10) -> dict:
    """Idle gaps labelled by the innermost host span of either prefix,
    idle time by label, device seconds per scope and the longest
    operations with their scope, over the traced window."""
    from bench import devtrace
    t0, t1 = devtrace.window_of(events)
    devices = sorted(events["device"])
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    host = sorted((h for h in events["host"]
                   if h[0] != devtrace.WINDOW_SPAN), key=lambda h: h[2])

    def label(s: int, e: int) -> str:
        mid = (s + e) // 2
        for name, hs, hd in host:              # innermost first
            if hs <= mid <= hs + hd:
                return name
        return "none"

    gaps, layer_ns, op_ns = [], {}, {}
    for dev in devices:
        clipped = []
        for name, start, dur, scope in events["device"][dev]:
            s, e = max(start, t0), min(start + dur, t1)
            if e <= s:
                continue
            clipped.append((s, e))
            layer_ns[scope] = layer_ns.get(scope, 0) + e - s
            op_ns[(name, scope)] = op_ns.get((name, scope), 0) + e - s
        edges = [t0] + [x for iv in devtrace.union(clipped) for x in iv] \
            + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    labelled = [(label(s, e), (e - s) / 1e9) for s, e in gaps]
    by_span: dict[str, float] = {}
    for name, d in labelled:
        by_span[name] = by_span.get(name, 0.0) + d / n
    stage_ns: dict[str, int] = {}
    for scope, ns in layer_ns.items():
        if scope.startswith("bireal/"):
            stage = "/".join(scope.split("/")[:2])
            stage_ns[stage] = stage_ns.get(stage, 0) + ns
    return {
        "window_s": (t1 - t0) / 1e9,
        "idle_gaps": [list(g) for g in
                      sorted(labelled, key=lambda g: -g[1])[:top]],
        "idle_by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "layer_s": {k: v / n / 1e9 for k, v in
                    sorted(layer_ns.items(), key=lambda kv: -kv[1])},
        "stage_s": {k: v / n / 1e9 for k, v in
                    sorted(stage_ns.items(), key=lambda kv: -kv[1])},
        "device_ops": [[k[0], k[1], v / n / 1e9] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
    }


# ------------------------------------------------------------------ a run
class _Hooks:
    """While entered: turns the engine's span log on as the window opens,
    notes the window's clock origin, and reads the device trace once
    more. The harness is left as it was on exit."""

    def __init__(self, harness, recorder: bool):
        self.harness, self.recorder = harness, recorder
        self.engine = self.sched = None
        self.t0 = 0.0
        self.events = None
        self.xspace = b""

    def __enter__(self):
        h = self.harness
        self._saved = (h.drive_open, h.drive_closed, h.devtrace.extract)
        h.drive_open = self._drive(h.drive_open)
        h.drive_closed = self._drive(h.drive_closed)
        extract_bench = h.devtrace.extract

        def both(xplane):
            self.xspace = Path(xplane).read_bytes()
            self.events = extract(xplane)
            return extract_bench(xplane)
        h.devtrace.extract = both
        return self

    def __exit__(self, *exc) -> None:
        h = self.harness
        h.drive_open, h.drive_closed, h.devtrace.extract = self._saved

    def _drive(self, drive):
        def wrapped(system, sched, seconds, **kw):
            # the system lets its engine go when the run ends
            self.engine, self.sched = system.engine, sched
            if self.recorder:
                self.engine.spans.enable()
            self.t0 = time.perf_counter()
            return drive(system, sched, seconds, **kw)
        return wrapped


def report(workload: str, seed: int, seconds: float, trace: bool,
           recorder: bool, *, root: Path = ROOT, need_chip: bool = True,
           out: Path | None = None) -> dict:
    from bench import harness
    keep: dict = {}
    with _Hooks(harness, recorder) as hooks:
        result = harness.run_cell(workload, seed, seconds, trace, root=root,
                                  need_chip=need_chip, keep=keep)
    run = keep["run"]
    engine = hooks.engine
    line = {"result": result,
            "engine_step_ms": harness.metric_reader(
                root, "engine_step_ms.online").read(run),
            "bulk_input_counts": engine.batch_input_counts}
    if recorder:
        spans = window_spans(engine.spans.read(), hooks.t0, run.steady_end)
        top = ("engine.step" if hooks.sched.loop == "open"
               else "engine.classify_batch")
        rep = host_and_wait(spans, top)
        rep["dropped"] = engine.spans.dropped
        stalls = harness.stalls(run, hooks.sched)
        intervals = None
        if hooks.sched.loop != "open":      # a stall is a long call
            starts = {s[1]: (s[1], s[2]) for s in run.spans}
            intervals = [starts[at] for at, _ in stalls]
        every = window_spans(engine.spans.read(), hooks.t0, run.seconds)
        rep["stalls"] = stall_spans(stalls, every, intervals)
        harness.log("longest stalls (at s, for s, in span): " + ", ".join(
            f"{at:.3f} {d:.4f} {name}" for at, d, name, _ in rep["stalls"]))
        line["spans"] = rep
    if hooks.events is not None and hooks.events["device"]:
        line["trace"] = reduce(hooks.events)
    if out is not None:
        out.mkdir(parents=True, exist_ok=True)
        name = (f"{workload}-seed-{seed}-trace-{int(trace)}"
                f"-rec-{int(recorder)}.json")
        dump = dict(line)
        if hooks.events is not None:
            dump["trace_sample"] = hooks.events["sample"]
        (out / name).write_text(json.dumps(dump, indent=1))
        if hooks.xspace:
            # the trace itself, for reading by hand
            (out / name).with_suffix(".xplane.pb.gz").write_bytes(
                gzip.compress(hooks.xspace))
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--recorder", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    line = report(args.workload, args.seed, args.seconds, bool(args.trace),
                  bool(args.recorder), out=args.out)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
