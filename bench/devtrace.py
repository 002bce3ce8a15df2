"""Device trace: capture with JAX's profiler, extract events, reduce.

``extract`` turns one ``.xplane.pb`` into plain lists: the operations each
TPU ran (plane ``/device:TPU:<n>``, line ``XLA Ops``) and the benchmark's
own host spans (``TraceAnnotation`` names starting with ``bench.``).
``reduce`` works on those lists alone, so it is checked on a small recorded
trace without a chip:

* busy time is the union of a device's operation intervals inside the
  traced window (the host span ``bench.window``), averaged over devices;
* a kernel family's time is the summed duration of the operations whose
  HLO instruction is named after one of its kernels' entry points
  (``xnor_conv2d.5`` belongs to ``xnor_conv2d``);
* the idle gaps between busy intervals are labelled by the innermost
  benchmark host span that covers each gap's middle.
"""
from __future__ import annotations

import glob
import json
import os
from pathlib import Path

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_LINE = "XLA Ops"


def newest_xplane(directory: Path) -> Path:
    found = sorted(glob.glob(os.path.join(str(directory), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(found[-1])


def op_name(hlo_text: str) -> str:
    """``%xnor_conv2d.5 = s32[...] custom-call(...)`` -> ``xnor_conv2d.5``:
    the instruction's own name, without its operands (which name the
    instructions that feed it)."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def extract(xplane: Path) -> dict:
    """{"device": {plane: [[op, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}"""
    import jax
    data = jax.profiler.ProfileData.from_file(str(xplane))
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for e in line.events:
                    ops.append([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)])
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host}


def save(events: dict, path: Path) -> None:
    Path(path).write_text(json.dumps(events))


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) pairs."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(events: dict) -> tuple[int, int]:
    spans = [h for h in events["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    name, start, dur = max(spans, key=lambda h: h[2])
    return start, start + dur


def _clip(start: int, dur: int, t0: int, t1: int):
    s, e = max(start, t0), min(start + dur, t1)
    return (s, e) if e > s else None


def family(name: str) -> str:
    """``xnor_conv2d.5`` -> ``xnor_conv2d``: the op without its number."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def matches(op, names) -> bool:
    return family(op[0]) in names


def reduce(events: dict, kernels: dict[str, tuple[str, ...]] | None = None,
           top: int = 10) -> dict:
    """Busy and idle seconds, kernel-family seconds, top ops and the longest
    labelled idle gaps, over the traced window."""
    t0, t1 = window_of(events)
    window_ns = t1 - t0
    kernels = kernels or {}
    devices = sorted(events["device"])
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns, op_ns, fam_ns, fam_n, gaps = [], {}, {}, {}, []
    for dev in devices:
        clipped = []
        for op in events["device"][dev]:
            iv = _clip(op[1], op[2], t0, t1)
            if iv is None:
                continue
            clipped.append(iv)
            d = iv[1] - iv[0]
            op_ns[op[0]] = op_ns.get(op[0], 0) + d
            for fam, pats in kernels.items():
                if matches(op, pats):
                    fam_ns[fam] = fam_ns.get(fam, 0) + d
                    fam_n[fam] = fam_n.get(fam, 0) + 1
        merged = union(clipped)
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i], edges[i + 1]))
    n_dev = len(devices)
    host = sorted(events["host"], key=lambda h: h[2])   # innermost first

    def label(s: int, e: int) -> str:
        mid = (s + e) // 2
        for name, hs, hd in host:
            if name != WINDOW_SPAN and hs <= mid <= hs + hd:
                return name[len(SPAN_PREFIX):]
        return "none"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "devices": n_dev,
        "kernel_s": {f: v / n_dev / 1e9 for f, v in fam_ns.items()},
        "kernel_calls": {f: v / n_dev for f, v in fam_n.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in gaps[:top]],
    }
