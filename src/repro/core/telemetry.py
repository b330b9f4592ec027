"""Unified telemetry: the control-plane tracer and the metrics registry.

This repo prices everything it executes — `Program.cost_terms`,
`Sequencer.makespan`, `MeshMakespan` over `FabricOccupancy` — and does
its own control-plane work at trace time: schedule generation, the
selector's pricing, micro-op compile and verification. Two primitives
surface both:

:class:`Tracer`
    Spans + instant events on TWO clocks:

    * the **wall clock** (`time.perf_counter_ns`) for control-plane
      work (selector choices, schedule generation, compiles, verifies,
      engine drains).  Every span is also a profiler TraceMe of the
      same name (`jax.profiler.TraceAnnotation`), so a device trace
      captured around the work holds the span on the device's clock;
    * the **virtual clock** — priced seconds.  `interval()` records
      per-request and per-link occupancy windows (`simulate_drain`,
      `MeshMakespan.timeline()`), the same numbers the makespan model
      composes.

    `to_chrome_trace()` exports Chrome trace-event JSON (one track per
    queue, one per physical link, retry/fault instants as markers —
    loadable in Perfetto or ui.perfetto.dev); `snapshot()` flattens the
    event stream into a dict for asserts and logs.

:class:`MetricsRegistry`
    Typed counters/gauges plus structured per-step records.  The
    scattered `Selector.stats` / `Sequencer.stats` / `engine.stats`
    dicts are now read-compatible :class:`StatsView` mappings over a
    registry — existing `stats["issued"]` reads keep working, but
    writers go through `inc()`/`set()` (rule LC004 in
    `scripts/lint_conventions.py` flags new direct `.stats[...] =`
    writes).

Spans run only at trace time (they wrap Python control-plane work,
never a compiled program), so they are on whether or not a `Tracer` is
installed: the process-default tracer :data:`NULL` records no event,
but its spans still open the profiler annotation, and the outermost
engine span of each kind in :data:`ENGINE_SPANS` reports its wall
seconds as the `jax.monitoring` duration event
``/repro/engine/<kind>_duration`` — the channel JAX reports its own
compile time on.  Instrumentation guards argument assembly for events
only a recording tracer keeps (instants, intervals) with
`tracer.enabled`.  **Pricing never reads the tracer** — enabling
tracing cannot change a priced or executed bit (regression-gated by
tests/test_telemetry.py and the bench baseline).

Scoping::

    from repro.core import telemetry
    with telemetry.use(telemetry.Tracer()) as tr:
        ...  # everything issued/priced/drained here is recorded
    trace = tr.to_chrome_trace()

This module imports JAX's profiler and monitoring, and nothing from
`repro` — every core module may import it without cycles.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from collections.abc import Mapping
from typing import Iterator, Optional

import jax
from jax import monitoring
from jax.profiler import TraceAnnotation

__all__ = [
    "Tracer", "NullTracer", "MetricsRegistry", "StatsView",
    "NULL", "current", "use", "axis_label", "ENGINE_SPANS", "named_scope",
]


def named_scope(*names: str):
    """Decorator: trace each call under the device scopes `names`
    (`jax.named_scope`, outermost first).  The scopes are HLO metadata
    (`op_name`), free at run time, and name the call's ops in a device
    trace.  A fresh scope is entered per call: `jax.named_scope`'s own
    decorator form keeps one context object for every call, so a call
    that re-enters the same function leaves its scope on the name stack
    of whatever the caller traces next."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with contextlib.ExitStack() as stack:
                for name in names:
                    stack.enter_context(jax.named_scope(name))
                return fn(*args, **kwargs)
        return call
    return wrap


def axis_label(axis) -> str:
    """Human-readable track label for an axis key (str or tuple)."""
    if isinstance(axis, tuple):
        return "+".join(str(a) for a in axis)
    return str(axis)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

#: pid of the control-plane track group (wall clock, exported as us).
CONTROL_PID = 1
#: pid of the virtual-clock track group (priced seconds, exported as us).
VIRTUAL_PID = 2

#: Engine control-plane span name -> the kind its outermost occurrence
#: reports as ``/repro/engine/<kind>_duration`` (wall seconds).
ENGINE_SPANS = {
    "schedule": "schedule",
    "selector.choose": "choose",
    "compile": "compile",
    "verify": "verify",
}
MONITOR_PREFIX = "/repro/engine/"

# Open engine spans on this thread: only the outermost one reports, so
# a compile inside `selector.choose` is not counted twice.
_engine_depth = threading.local()


class _Span:
    """Context manager for one control-plane span: a profiler TraceMe,
    its wall duration, the monitoring event of an outermost engine span,
    and (under a recording tracer) one "X" event."""

    __slots__ = ("_tracer", "name", "track", "args", "_start", "_annot",
                 "_outermost")

    def __init__(self, tracer: Optional["Tracer"], name: str, track: str,
                 args: dict):
        self._tracer = tracer
        self.name = name
        self.track = track
        self.args = args
        self._start = 0
        self._annot = None
        self._outermost = False

    def add(self, **args) -> None:
        """Attach more args to the span (e.g. the outcome, post-hoc)."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        self._annot = TraceAnnotation(self.name)
        self._annot.__enter__()
        if self.name in ENGINE_SPANS:
            depth = getattr(_engine_depth, "n", 0)
            self._outermost = depth == 0
            _engine_depth.n = depth + 1
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = time.perf_counter_ns()
        self._annot.__exit__(exc_type, exc, tb)
        if self.name in ENGINE_SPANS:
            _engine_depth.n -= 1
            if self._outermost:
                monitoring.record_event_duration_secs(
                    f"{MONITOR_PREFIX}{ENGINE_SPANS[self.name]}_duration",
                    (end - self._start) * 1e-9)
        if self._tracer is not None:
            if exc_type is not None:
                self.args.setdefault("error", exc_type.__name__)
            self._tracer._events.append({
                "type": "span", "name": self.name, "track": self.track,
                "pid": CONTROL_PID, "ts": self._tracer._us(self._start),
                "dur": (end - self._start) * 1e-3, "args": self.args,
            })
        return False


class NullTracer:
    """The process-default tracer: records no event.

    `enabled` is False so instrumentation can skip argument assembly
    for instants and intervals; its spans still annotate the profiler
    and report engine monitoring events (see the module docstring).
    """

    enabled = False

    def span(self, name: str, track: str = "control", **args) -> _Span:
        return _Span(None, name, track, args)

    def instant(self, name: str, track: str = "control",
                ts_s: Optional[float] = None, **args) -> None:
        pass

    def interval(self, name: str, track: str, start_s: float, end_s: float,
                 **args) -> None:
        pass

    def ingest_timeline(self, timeline: dict) -> None:
        pass


#: The shared disabled tracer (the process default).
NULL = NullTracer()


class Tracer:
    """Recording tracer: spans and instants on the wall clock, virtual
    intervals on the priced clock.  See the module docstring for the
    event model."""

    enabled = True

    def __init__(self):
        self._events: list = []
        self._t0 = time.perf_counter_ns()
        # (pid, track) -> tid, assigned in first-use order
        self._tids: dict = {}
        self._installed_prev = []  # `with tracer:` scoping stack

    def _us(self, ns: int) -> float:
        """Wall microseconds since this tracer was made."""
        return (ns - self._t0) * 1e-3

    # -- recording ----------------------------------------------------------
    def span(self, name: str, track: str = "control", **args) -> _Span:
        """Open a control-plane span; use as a context manager.  The
        returned span's `add(**args)` attaches outcome fields before it
        closes.  Spans on one track are well-nested by construction
        (context-manager discipline on one monotone clock)."""
        return _Span(self, name, track, dict(args))

    def instant(self, name: str, track: str = "control",
                ts_s: Optional[float] = None, **args) -> None:
        """A marker: on the wall clock by default, or pinned to the
        virtual clock when `ts_s` (priced seconds) is given."""
        if ts_s is None:
            self._events.append({
                "type": "instant", "name": name, "track": track,
                "pid": CONTROL_PID,
                "ts": self._us(time.perf_counter_ns()), "args": args,
            })
        else:
            self._events.append({
                "type": "instant", "name": name, "track": track,
                "pid": VIRTUAL_PID, "ts": float(ts_s), "args": args,
            })

    def interval(self, name: str, track: str, start_s: float, end_s: float,
                 **args) -> None:
        """A virtual-clock occupancy window (priced seconds): one
        request on a queue track, or one program's wire seconds on a
        physical-link track."""
        self._events.append({
            "type": "interval", "name": name, "track": track,
            "pid": VIRTUAL_PID, "ts": float(start_s),
            "dur": float(end_s) - float(start_s), "args": args,
        })

    def ingest_timeline(self, timeline: dict) -> None:
        """Record a `MeshMakespan.timeline()` as virtual-clock intervals:
        per-queue drain windows, chain-placed per-request windows, and
        serialized per-link busy windows (+ the trailing alpha term)."""
        for q in timeline.get("queues", ()):
            self.interval("drain", q["track"], q["start_s"], q["end_s"],
                          axis=axis_label(q["axis"]))
        for r in timeline.get("requests", ()):
            self.interval(r.get("name", "request"), r["track"],
                          r["start_s"], r["end_s"], rids=r["rids"],
                          full_s=r["full_s"], lat_s=r["lat_s"],
                          wire_s=r["wire_s"], coalesced=r["coalesced"])
        for lk in timeline.get("links", ()):
            self.interval(lk.get("name", "wire"), lk["track"],
                          lk["start_s"], lk["end_s"])

    # -- scoping ------------------------------------------------------------
    def __enter__(self) -> "Tracer":
        global _ACTIVE
        self._installed_prev.append(_ACTIVE)
        _ACTIVE = self
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        global _ACTIVE
        _ACTIVE = self._installed_prev.pop()
        return False

    # -- export -------------------------------------------------------------
    def _tid(self, pid: int, track: str) -> int:
        key = (pid, track)
        tid = self._tids.get(key)
        if tid is None:
            tid = len(self._tids) + 1
            self._tids[key] = tid
        return tid

    def to_chrome_trace(self) -> dict:
        """Chrome trace-event JSON (the `{"traceEvents": [...]}` form).

        Control-plane events live under pid 1 (wall microseconds since
        the tracer was made), virtual-clock events under pid 2 (1 priced
        second == 1e6 us).  Each track is a named thread; events are
        sorted by (pid, tid, ts) so per-track timestamps are monotone.
        Load the file in Perfetto (ui.perfetto.dev) or chrome://tracing,
        or summarize it with `scripts/trace_report.py`.
        """
        events = []
        for ev in self._events:
            pid = ev["pid"]
            tid = self._tid(pid, ev["track"])
            scale = 1.0 if pid == CONTROL_PID else 1e6
            ts = float(ev["ts"]) * scale
            if ev["type"] in ("span", "interval"):
                events.append({"ph": "X", "name": ev["name"], "cat": "repro",
                               "pid": pid, "tid": tid, "ts": ts,
                               "dur": float(ev["dur"]) * scale,
                               "args": ev["args"]})
            else:  # instant
                events.append({"ph": "i", "name": ev["name"], "cat": "repro",
                               "pid": pid, "tid": tid, "ts": ts, "s": "t",
                               "args": ev["args"]})
        events.sort(key=lambda e: (e["pid"], e["tid"], e["ts"],
                                   -e.get("dur", 0.0)))
        meta = [
            {"ph": "M", "name": "process_name", "pid": CONTROL_PID, "tid": 0,
             "args": {"name": "control-plane (wall clock)"}},
            {"ph": "M", "name": "process_name", "pid": VIRTUAL_PID, "tid": 0,
             "args": {"name": "virtual-clock (priced seconds)"}},
        ]
        for (pid, track), tid in sorted(self._tids.items(),
                                        key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": tid, "args": {"name": track}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def snapshot(self) -> dict:
        """Flat summary of the event stream: per-name span/interval
        counts and total durations (spans in wall microseconds,
        intervals in priced seconds), instant counts, and the total
        event count."""
        out: dict = {"events": len(self._events)}
        for ev in self._events:
            if ev["type"] in ("span", "interval"):
                k = f"{ev['type']}.{ev['name']}.count"
                out[k] = out.get(k, 0) + 1
                kd = f"{ev['type']}.{ev['name']}.total"
                out[kd] = out.get(kd, 0.0) + float(ev["dur"])
            else:
                k = f"instant.{ev['name']}.count"
                out[k] = out.get(k, 0) + 1
        return out


# ---------------------------------------------------------------------------
# Process-default tracer + scoping
# ---------------------------------------------------------------------------

_ACTIVE = NULL


def current():
    """The tracer instrumentation should record to right now (the
    :data:`NULL` no-op tracer unless a `use()` / `with tracer:` scope is
    active)."""
    return _ACTIVE


@contextlib.contextmanager
def use(tracer):
    """Install `tracer` as the process tracer for the dynamic extent of
    the `with` block (restores the previous one on exit)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    try:
        yield tracer
    finally:
        _ACTIVE = prev


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

class StatsView(Mapping):
    """Live read-compatible mapping over a :class:`MetricsRegistry`.

    Drop-in for the legacy ad-hoc `.stats` dicts: supports `[]`,
    `.get`, iteration, `len`, and equality with plain dicts.  Writing
    through the view delegates to `registry.set` (an out-of-tree
    back-compat shim — in-tree code emits through the registry, and
    LC004 flags new direct `.stats[...] =` writes in src/).
    """

    __slots__ = ("_reg",)

    def __init__(self, registry: "MetricsRegistry"):
        self._reg = registry

    def __getitem__(self, name: str):
        return self._reg._values[name]

    def __iter__(self) -> Iterator[str]:
        return iter(self._reg._values)

    def __len__(self) -> int:
        return len(self._reg._values)

    def __setitem__(self, name: str, value) -> None:
        self._reg.set(name, value)

    def __repr__(self) -> str:
        return f"StatsView({dict(self._reg._values)!r})"


class MetricsRegistry:
    """Typed counters/gauges + structured records, behind mapping views.

    `counter(name)` declares a monotone counter (so the key is present,
    at 0, before the first `inc` — tests read counters on fresh
    objects); `set(name, value)` writes a gauge, declaring it on first
    write.  `record(**fields)` appends one structured record (the
    trainer emits one per step).  `view()` returns the live
    :class:`StatsView` components expose as `.stats`.
    """

    __slots__ = ("_values", "_kinds", "_records")

    def __init__(self):
        self._values: dict = {}
        self._kinds: dict = {}
        self._records: list = []

    def __repr__(self) -> str:
        return f"MetricsRegistry({self._values!r})"

    # -- counters / gauges ---------------------------------------------------
    def counter(self, name: str, value=0) -> None:
        """Declare (or reset) a monotone counter."""
        self._kinds[name] = "counter"
        self._values[name] = value

    def inc(self, name: str, delta=1):
        """Increment a counter (declared on first use); returns the new
        value."""
        val = self._values.get(name, 0) + delta
        self._kinds.setdefault(name, "counter")
        self._values[name] = val
        return val

    def set(self, name: str, value) -> None:
        """Write a gauge (declared on first write)."""
        self._kinds.setdefault(name, "gauge")
        self._values[name] = value

    def get(self, name: str, default=None):
        return self._values.get(name, default)

    def discard(self, name: str) -> None:
        """Remove a metric entirely (its key disappears from views)."""
        self._values.pop(name, None)
        self._kinds.pop(name, None)

    # -- structured records --------------------------------------------------
    def record(self, **fields) -> dict:
        """Append one structured record (e.g. a per-step metrics row);
        returns it."""
        rec = dict(fields)
        self._records.append(rec)
        return rec

    def records(self) -> list:
        return list(self._records)

    # -- views ---------------------------------------------------------------
    def snapshot(self) -> dict:
        """A flat copy of every metric value."""
        return dict(self._values)

    def view(self) -> StatsView:
        return StatsView(self)
