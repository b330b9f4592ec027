"""Device time by the program's own scopes, and idle time by the host
events around it.

The program names its layers with `jax.named_scope`: `dlrm.lookup`,
`dlrm.fc<i>`, `engine.<collective>` with `algo.<algorithm>` inside it,
and `uop.<kind>` for each micro-op `execute_program` runs. The scopes are
HLO metadata: each instruction of a compiled program carries its name
stack in `op_name`, and a device trace names each operation by its
instruction. So `scope_map` reads the compiled HLO text of each program
(`opclass.parse_hlo`), and `reduce` puts every device operation of the
traced window under its scopes.

- An operation's *owner* is its outermost `engine.*` scope if it has
  one, else its outermost `dlrm.*` scope, else "" (unscoped): an engine
  call made inside `dlrm.lookup` counts as engine time, not lookup time.
- Time per owner and per scope path is the union of the operations'
  intervals (an operation nested in a `while` is not counted twice),
  averaged over the devices traced.

`load` also keeps every host event of the profile but the Python
tracer's (`$file:line` names), and `reduce` splits each device's idle
time by what the host was doing: each instant of an idle gap goes to
the innermost (shortest) host event open at that instant, from any
host thread: a runtime event (allocation, transfer, launch, completion),
a control-plane span of the program, or, where neither is open, the
benchmark's own annotation (`host: wait`). The profile's host and
device clocks disagree by a few hundred microseconds (a step's program
starts on the device before the host has enqueued it), so the host
events are first shifted by the offset, searched over +-3 ms, at which
the runtime's events best cover the device's idle time; it is reported
per device as `clock_shift_us`. In a steady loop the offset is known
only up to whole steps, which moves no total.

`bench/run.py` does not call this module yet (it reads the layers by
data flow, `bench/opclass.py`). Run a cell traced with the scopes read:

  python3 -m bench.scopes --workload <name> --seed <n> --seconds <s>

It runs `bench/run.py`'s traced run in this process, with JAX's compile
cache keyed by metadata too, and prints its result line with a `scopes`
object added: the readings `lookup_ms.scope`,
`fc_ms.scope`, `coll_engine_us.small` and `engine_setup_s` (the
program's `/repro/engine/*` monitoring seconds in set-up), the top scope
paths, the idle causes, and the traced window's end-to-end metrics.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

import jax
import numpy as np

from bench.opclass import instr_name, parse_hlo
from bench.trace import HOST_LABELS, WINDOW, module_name

# The name-stack components that are the program's scopes.
SCOPE_PREFIXES = ("dlrm.", "engine.", "algo.", "uop.")
UNSCOPED = "(unscoped)"
NO_EVENT = "(no host event)"
SHIFT_RANGE_NS = 3_000_000
MIN_HOST_GAP_NS = 10_000
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


def scopes_of(op_name: str) -> tuple:
    """'jit(f)/shard_map/dlrm.lookup/engine.allreduce/while/body/add' ->
    ('dlrm.lookup', 'engine.allreduce')."""
    return tuple(c for c in op_name.split("/")
                 if c.startswith(SCOPE_PREFIXES))


def scope_map(text: str) -> dict:
    """Compiled HLO text -> {instruction name: scope path}. An
    instruction without an op_name takes its caller's (the `while` or
    call that runs its computation)."""
    hlo = parse_hlo(text)
    comps = hlo["comps"]
    out, inherited = {}, {hlo["entry"]: ""}
    order = [hlo["entry"]]
    for comp in order:
        for ins in comps.get(comp, ()):
            name = ins["op_name"] or inherited.get(comp, "")
            out[ins["name"]] = scopes_of(name)
            for c in ins["callees"]:
                if c not in inherited:
                    inherited[c] = name
                    order.append(c)
    return out


def owner(path: tuple) -> str:
    for prefix in ("engine.", "dlrm."):
        for c in path:
            if c.startswith(prefix):
                return c
    return ""


def load(out_dir: str) -> dict:
    """The profile under `out_dir` as plain lists:
    {"devices": {id: {"modules": [[name, start_ns, dur_ns]],
                      "ops": [[name, start_ns, dur_ns]]}},
     "host": [[name, start_ns, dur_ns]],       # every host event kept
     "window": [start_ns, dur_ns] or None}."""
    files = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {"devices": {}, "host": [], "window": None}
    if not files:
        return out
    data = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
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
            out["devices"][m.group(2)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == WINDOW:
                        out["window"] = [e.start_ns, e.duration_ns]
                    elif not name.startswith("$"):
                        out["host"].append([name, e.start_ns,
                                            e.duration_ns])
    return out


def reduce(trace: dict, maps: dict, top: int = 10) -> dict | None:
    """Seconds per scope in the window, and idle seconds per host event.

    `maps` is {module: scope_map(its compiled HLO)}. Returns None where
    the trace holds no window or no device operation; else
      {"owners": {module: {owner: s}},   # owner "" = unscoped
       "paths": {"a/b/c": s},            # full scope path, top first
       "idle_causes": [[label, s]],      # top `top`, averaged over devices
       "clock_shift_us": [us]}           # per device
    """
    devs = [d for d in trace["devices"].values() if d["ops"]]
    if trace["window"] is None or not devs:
        return None
    lo = trace["window"][0]
    hi = lo + trace["window"][1]
    # an event that spans the whole window says nothing of one gap
    host = [(s, s + d, "host: " + name if name in HOST_LABELS else name)
            for name, s, d in trace["host"] if s > lo or s + d < hi]
    runtime = _union([(s, e) for s, e, name in host
                      if not name.startswith("host: ")])
    owners, paths, causes, shifts = {}, {}, {}, []
    share = 1.0 / len(devs)
    for dev in devs:
        mods = sorted((m[1], m[1] + m[2], module_name(m[0]))
                      for m in dev["modules"] if lo <= m[1] < hi)
        by_owner, by_path, busy = {}, {}, []
        mi = 0
        for name, s, d in sorted(dev["ops"], key=lambda o: o[1]):
            s, e = max(s, lo), min(s + d, hi)
            if e <= s:
                continue
            busy.append((s, e))
            while mi < len(mods) and mods[mi][1] <= s:
                mi += 1
            mod = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s \
                else ""
            path = maps.get(mod, {}).get(instr_name(name), ())
            by_owner.setdefault((mod, owner(path)), []).append((s, e))
            by_path.setdefault("/".join(path) or UNSCOPED, []).append(
                (s, e))
        for (mod, own), ivs in by_owner.items():
            rec = owners.setdefault(mod, {})
            rec[own] = rec.get(own, 0.0) + _length(ivs) * share * 1e-9
        for p, ivs in by_path.items():
            paths[p] = paths.get(p, 0.0) + _length(ivs) * share * 1e-9
        gaps = _gaps(_union(busy), lo, hi)
        shift = clock_shift(gaps, runtime)
        shifts.append(shift * 1e-3)
        for label, ns in label_idle(gaps, host, shift).items():
            causes[label] = causes.get(label, 0.0) + ns * share * 1e-9
    return {
        "owners": owners,
        "paths": dict(sorted(paths.items(), key=lambda kv: -kv[1])),
        "idle_causes": [[k, v] for k, v in sorted(
            causes.items(), key=lambda kv: -kv[1])[:top]],
        "clock_shift_us": shifts,
    }


def _length(ivs) -> int:
    """Length of the union of intervals."""
    return sum(e - s for s, e in _union(ivs))


def _union(ivs) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(busy, lo, hi) -> list:
    """The idle intervals of [lo, hi) around merged `busy` intervals."""
    out, prev = [], lo
    for s, e in busy:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        out.append((prev, hi))
    return out


def _covered(b):
    """F(x) = length of merged, sorted intervals `b` left of x, as a
    vectorized function of x."""
    starts = np.asarray([s for s, _ in b], dtype=np.int64)
    lens = np.asarray([e - s for s, e in b], dtype=np.int64)
    before = np.concatenate([[0], np.cumsum(lens)[:-1]])

    def f(x):
        i = np.maximum(np.searchsorted(starts, x, side="right") - 1, 0)
        return before[i] + np.clip(x - starts[i], 0, lens[i])
    return f


def clock_shift(gaps, runtime) -> int:
    """ns to take off the host's clock so that the runtime's host events
    (merged intervals) cover the device's idle `gaps` of 10 us or more
    (shorter ones are the device's own, between ops) most: a grid of
    0.1 ms over +-3 ms, then 10 us around its best; 0 without events."""
    gaps = np.asarray([g for g in gaps if g[1] - g[0] >= MIN_HOST_GAP_NS],
                      dtype=np.int64).reshape(-1, 2)
    if not runtime or not len(gaps):
        return 0
    f = _covered(runtime)

    def covered(shift):
        return int((f(gaps[:, 1] + shift) - f(gaps[:, 0] + shift)).sum())

    best = max(range(-SHIFT_RANGE_NS, SHIFT_RANGE_NS + 1, 100_000),
               key=lambda sh: (covered(sh), -abs(sh)))
    return max(range(best - 100_000, best + 100_001, 10_000),
               key=lambda sh: (covered(sh), -abs(sh)))


def label_idle(gaps, host, shift: int) -> dict:
    """Idle ns by label: each instant of a gap goes to the innermost
    (shortest) host event (start, end, name) open then, after `shift`;
    NO_EVENT where none is."""
    events = sorted((s - shift, e - shift, name) for s, e, name in host)
    points = sorted({p for s, e, _ in events for p in (s, e)}
                    | {p for g in gaps for p in g})
    out, active, j, g = {}, [], 0, 0
    for a, b in zip(points, points[1:]):
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps):
            break
        while j < len(events) and events[j][0] <= a:
            active.append(events[j])
            j += 1
        active = [ev for ev in active if ev[1] > a]
        s, e = max(a, gaps[g][0]), min(b, gaps[g][1])
        if e > s:
            label = min(active, key=lambda ev: ev[1] - ev[0])[2] \
                if active else NO_EVENT
            out[label] = out.get(label, 0) + (e - s)
    return out


def owner_s(scoped: dict | None, prefix: str, modules=None) -> float | None:
    """Seconds of the owners starting with `prefix` (in `modules`, or
    all); None where no operation had such an owner."""
    if scoped is None:
        return None
    total, found = 0.0, False
    for mod, rec in scoped["owners"].items():
        if modules is not None and mod not in modules:
            continue
        for own, s in rec.items():
            if own.startswith(prefix):
                total += s
                found = True
    return total if found else None


def readings(scoped: dict | None, ctx: dict) -> dict:
    """The per-layer readings of the scopes, where something is there to
    read: `ctx` holds the run's `trace` (`bench.trace.reduce`), `window`,
    `work` and `program_s` ({monitoring event: seconds in set-up})."""
    out = {}
    engine = [v for k, v in ctx["program_s"].items()
              if k.startswith("/repro/engine/")]
    if engine:
        out["engine_setup_s"] = sum(engine)
    if scoped is None or ctx["trace"] is None:
        return out
    batches = ctx["window"].get("batches")
    if batches:
        for name, prefix in (("lookup_ms.scope", "dlrm.lookup"),
                             ("fc_ms.scope", "dlrm.fc")):
            s = owner_s(scoped, prefix)
            if s is not None:
                out[name] = s / batches * 1e3
    programs = ctx["work"].get("programs")
    if programs:
        small = [p["module"] for p in programs if p["cls"] == "small"]
        s = owner_s(scoped, "engine.", small)
        calls = sum(ctx["trace"]["modules"][m]["count"] for m in small
                    if m in ctx["trace"]["modules"]) * ctx["work"]["chain"]
        if s is not None and calls:
            out["coll_engine_us.small"] = s / calls * 1e6
    return out


def hlo_texts(drv) -> dict:
    """{module name: compiled HLO text} of the programs a driver runs (a
    persistent compile cache makes these loads, not compiles)."""
    if hasattr(drv, "programs"):        # the collective grid
        return {p["module"]: p["fn"].lower(x).compile().as_text()
                for p, x in zip(drv.programs, drv.inputs)}
    ids = jax.device_put(drv.pool[0], drv.ids_sharding)
    text = drv.serve.lower(drv.params, ids).compile().as_text()
    return {parse_hlo(text)["module"]: text}


def main(argv=None, **run_kw) -> int:
    """One traced run of `bench/run.py` with the scopes read; prints its
    result line with `scopes` added. `run_kw` go to `run.run` (tests)."""
    from bench import run, trace

    program_s, grabbed = {}, {}

    def listen(event, duration, **_):
        if event.startswith("/repro/"):
            program_s[event] = program_s.get(event, 0.0) + duration

    def patch(drv):
        warm, classes, e2e = drv.warm, drv.classes, drv.end_to_end

        def warm_then_snapshot():
            warm()
            grabbed["program_s"] = dict(program_s)

        def classes_and_texts():
            grabbed["maps"] = {m: scope_map(t)
                               for m, t in hlo_texts(drv).items()}
            return classes()

        def end_to_end(win):
            grabbed["window"] = {k: v for k, v in win.items()
                                 if isinstance(v, (int, float))}
            grabbed["end_to_end"] = e2e(win)
            return grabbed["end_to_end"]

        drv.warm, drv.classes = warm_then_snapshot, classes_and_texts
        drv.end_to_end = end_to_end
        grabbed["work"] = drv.work

    trace_load = trace.load

    def load_both(out_dir):
        grabbed["profile"] = load(out_dir)
        loaded = trace_load(out_dir)
        grabbed["trace"] = trace.reduce(loaded, {})
        return loaded

    # JAX's persistent cache keys a program without its metadata: a copy
    # compiled without the scopes (or with others) would be loaded in
    # place of this one, and its ops would read as unscoped
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.monitoring.register_event_duration_secs_listener(listen)
    trace.load = load_both
    try:
        result = run.run(list(argv if argv is not None else sys.argv[1:])
                         + ["--trace", "1"], patch=patch, **run_kw)
    except run.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        trace.load = trace_load
        jax.monitoring.unregister_event_duration_listener(listen)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)
    scoped = reduce(grabbed["profile"], grabbed["maps"])
    ctx = {"trace": grabbed["trace"], "window": grabbed["window"],
           "work": grabbed["work"](), "program_s": grabbed["program_s"]}
    result["scopes"] = {
        "metrics": readings(scoped, ctx),
        "program_s": grabbed["program_s"],
        "paths": list(scoped["paths"].items())[:12] if scoped else [],
        "idle_causes": scoped["idle_causes"] if scoped else [],
        "clock_shift_us": scoped["clock_shift_us"] if scoped else [],
        "traced_end_to_end": grabbed["end_to_end"],
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
