"""Device trace: capture, load, and the reduction from trace to numbers.

`capture` runs JAX's profiler around the traced window; `load` reads the
`.xplane.pb` it wrote into plain lists (what `bench/tests` keeps a
recorded excerpt of); `reduce` turns those lists into the device's busy
time, the time of each class of operation, each program's executions, and
the breakdown of device time and idle gaps. Every per-layer metric is read
from `reduce`'s result, so all of them are computed one way.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

import jax

from bench.opclass import instr_name

# The host annotations the benchmark writes around each step of a window;
# an idle gap on the device is labelled by the one it overlaps most.
HOST_LABELS = ("ids_to_device", "dispatch", "wait")
WINDOW = "bench_window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


@contextlib.contextmanager
def capture(out_dir: str):
    """Profile the block into `out_dir` (a fresh directory)."""
    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(out_dir: str) -> dict:
    """The trace under `out_dir` as plain lists:
    {"devices": {id: {"modules": [[name, start_ns, dur_ns]],
                      "ops": [[name, start_ns, dur_ns]]}},
     "host": [[label, start_ns, dur_ns]]}."""
    files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {"devices": {}, "host": []}
    data = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, host = {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            devices[m.group(2)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name in HOST_LABELS or e.name == WINDOW]
    return {"devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def module_name(event_name: str) -> str:
    """'jit_dlrm_serve_step(1073...)' -> 'jit_dlrm_serve_step'."""
    return event_name.split("(", 1)[0]


def reduce(trace: dict, classes: dict, top: int = 10) -> dict | None:
    """Numbers from a loaded trace, within the host's `bench_window` span.

    `classes` maps module name -> {instruction name: class}
    (`opclass.classify`). Times are averaged over the devices traced.
    Returns None where the trace holds no device operation.
    """
    win = [h for h in trace["host"] if h[0] == WINDOW]
    devs = [d for d in trace["devices"].values() if d["ops"]]
    if not win or not devs:
        return None
    lo = win[0][1]
    hi = lo + win[0][2]
    busy = 0.0
    per_class, per_module, per_op = {}, {}, {}
    gaps_by_label = {}
    for i, dev in enumerate(devs):
        mods = sorted((m[1], m[1] + m[2], module_name(m[0]))
                      for m in dev["modules"] if lo <= m[1] < hi)
        for s, e, name in mods:
            rec = per_module.setdefault(name, {"count": 0, "span_ns": 0.0})
            rec["span_ns"] += (e - s) / len(devs)
            if i == 0:
                rec["count"] += 1
        ivs = []
        mi = 0
        for name, s, d in sorted(dev["ops"], key=lambda o: o[1]):
            s, e = _clip(s, s + d, lo, hi)
            if e <= s:
                continue
            ivs.append((s, e))
            while mi < len(mods) and mods[mi][1] <= s:
                mi += 1
            mod = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s \
                else ""
            ins = instr_name(name)
            cls = classes.get(mod, {}).get(ins, "other")
            per_class[cls] = per_class.get(cls, 0.0) + (e - s) / len(devs)
            key = f"{mod}:{ins} [{cls}]"
            per_op[key] = per_op.get(key, 0.0) + (e - s) / len(devs)
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged) / len(devs)
        if i == 0:
            gaps_by_label = _label_gaps(merged, trace["host"], lo, hi)
    ns = 1e-9
    return {
        "busy_s": busy * ns,
        "window_s": (hi - lo) * ns,
        "classes_s": {k: v * ns for k, v in per_class.items()},
        "modules": {k: {"count": v["count"], "span_s": v["span_ns"] * ns}
                    for k, v in per_module.items()},
        "breakdown": {
            "device_ops": [[k, v * ns] for k, v in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v * ns] for k, v in sorted(
                gaps_by_label.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def _label_gaps(busy, host, lo, hi) -> dict:
    """Idle time of one device by what the host was doing: each gap
    between busy intervals goes to the host label it overlaps most."""
    spans = sorted((s, s + d, name) for name, s, d in host
                   if name in HOST_LABELS)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    out, j = {}, 0
    for gs, ge in gaps:
        while j < len(spans) and spans[j][1] <= gs:
            j += 1
        best, label = 0, "host: other"
        k = j
        while k < len(spans) and spans[k][0] < ge:
            ov = min(ge, spans[k][1]) - max(gs, spans[k][0])
            if ov > best:
                best, label = ov, "host: " + spans[k][2]
            k += 1
        out[label] = out.get(label, 0) + (ge - gs)
    return out


def idle_share(ctx) -> float | None:
    """Share of the traced window in which no operation ran on the
    device (%), averaged over the chips; None without a trace."""
    tr = ctx["trace"]
    if tr is None:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
