"""Unified telemetry (core/telemetry.py).

Five contracts:

  * tracer semantics — process-default tracer records nothing, `use()`
    scoping, spans / instants, and a Chrome trace export whose
    control-plane spans are well-nested and whose per-track timestamps
    are monotone;
  * control-plane spans on the wall clock: each carries its wall
    duration, is a host event of a captured profile under its own name,
    and the outermost engine span of a kind reports one
    `/repro/engine/<kind>_duration` monitoring event;
  * the `MetricsRegistry` behind every legacy `.stats` view stays
    read-compatible (mapping equality with plain dicts, live reads);
  * `MeshMakespan.timeline()` reconstructs the composed makespan
    BITWISE — the max interval end equals `mesh_makespan_s` with `==`,
    across single-queue, shared-link, disjoint-fabric, dep-chained, and
    tiered-fault scenarios;
  * observability is read-only: enabling a tracer changes no priced or
    simulated bit (pricing never reads the tracer).
"""
import contextlib
import glob
import importlib.util
import json
import pathlib
import re
import time
import types

import jax
import jax.numpy as jnp

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import (
    CollectiveEngine, Communicator, FaultPlan, FaultyTransport, MeshMakespan,
    PricingEnv, Selector, TIERS, TransportTimeout, telemetry,
)
from repro.core import algorithms as A
from repro.core.sequencer import Request, Sequencer

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, _ROOT / "scripts" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def eng8(mesh8):
    return CollectiveEngine(mesh8)


@pytest.fixture()
def eng222(mesh222):
    return CollectiveEngine(mesh222)


def _fill(seq, axis, nbytes, n=4, collective="allreduce"):
    for _ in range(n):
        seq.issue(collective, np.zeros((nbytes // 4,), np.float32), axis)


def _feeds(reqs, seed, n=8):
    rng = np.random.default_rng(seed)
    return {r: [rng.integers(-20, 20, size=r.operand.shape)
                .astype(r.dtype) for _ in range(n)]
            for r in reqs if not isinstance(r.operand, Request)}


# --------------------------------------------------------------------------
# Tracer semantics
# --------------------------------------------------------------------------

def test_default_tracer_is_noop():
    tr = telemetry.current()
    assert tr is telemetry.NULL and not tr.enabled
    with tr.span("x", a=1) as sp:   # records nothing, never raises
        sp.add(b=2)
    tr.instant("x")
    tr.interval("i", "track", 0.0, 1.0)
    tr.ingest_timeline({"queues": [], "requests": [], "links": []})


def test_use_scoping_nests_and_restores():
    outer, inner = telemetry.Tracer(), telemetry.Tracer()
    assert telemetry.current() is telemetry.NULL
    with telemetry.use(outer):
        assert telemetry.current() is outer
        with telemetry.use(inner):
            assert telemetry.current() is inner
        assert telemetry.current() is outer
    assert telemetry.current() is telemetry.NULL


def test_span_records_args_exceptions_and_snapshot():
    tr = telemetry.Tracer()
    with tr.span("work", track="t", phase="a") as sp:
        sp.add(outcome="ok")
    with pytest.raises(RuntimeError):
        with tr.span("work", track="t"):
            raise RuntimeError("boom")
    tr.instant("mark", track="t", detail=1)
    snap = tr.snapshot()
    assert snap["span.work.count"] == 2
    assert snap["instant.mark.count"] == 1
    failed = [e for e in tr._events
              if e["type"] == "span" and "error" in e["args"]]
    assert len(failed) == 1 and failed[0]["args"]["error"] == "RuntimeError"


def test_spans_carry_wall_durations_in_order_and_nested():
    tr = telemetry.Tracer()
    with tr.span("outer", track="t"):
        tr.instant("before", track="t")
        with tr.span("inner", track="t"):
            time.sleep(0.02)
        tr.instant("after", track="t")
    outer, inner = (next(e for e in tr._events if e["name"] == n)
                    for n in ("outer", "inner"))
    before, after = (next(e for e in tr._events if e["name"] == n)
                     for n in ("before", "after"))
    # wall microseconds: the sleep shows in both spans' durations
    assert inner["dur"] >= 2e4 and outer["dur"] >= inner["dur"]
    assert outer["ts"] <= before["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= after["ts"]
    assert after["ts"] <= outer["ts"] + outer["dur"]
    snap = tr.snapshot()
    assert snap["span.inner.total"] >= 2e4


def test_spans_are_host_events_of_a_captured_profile(tmp_path, eng8,
                                                     monkeypatch):
    """With no tracer installed, the control plane's spans still land in
    a profile captured around the work, under their own names."""
    from repro.core import program as program_mod
    monkeypatch.setattr(program_mod, "_COMPILE_CACHE", {})
    monkeypatch.setattr(program_mod, "_VERIFIED", {})
    assert telemetry.current() is telemetry.NULL
    jax.profiler.start_trace(str(tmp_path))
    try:
        Selector().choose("allgather", 3 << 14, eng8.comm("x"))
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"selector.choose", "compile", "verify"} <= names


def test_outermost_engine_spans_report_monitoring_events(eng8):
    """One `/repro/engine/<kind>_duration` event per outermost engine
    span: the compiles and verifies inside `selector.choose` are part of
    its seconds, never counted again."""
    from repro.core import program as program_mod
    seen = []

    def listen(event, duration, **_):
        if event.startswith(telemetry.MONITOR_PREFIX):
            seen.append((event, duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        sel = Selector()
        sel.choose("allreduce", 5 << 14, eng8.comm("x"))
        choose = list(seen)
        sel.choose("allreduce", 5 << 14, eng8.comm("x"))   # memoized
        memo = seen[len(choose):]
        sched = eng8._cached_schedule("reduce_scatter", "ring",
                                      eng8.comm("x"), 0, "add")
        program_mod._COMPILE_CACHE.pop((sched, 3, None, True, True), None)
        program_mod._VERIFIED.pop((sched, 3, None, True, True), None)
        del seen[:]
        program_mod.compile_schedule(sched, segments=3)
        compiled = list(seen)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert [e for e, _ in choose] == ["/repro/engine/choose_duration"]
    assert choose[0][1] > 0.0
    assert memo == []
    assert [e for e, _ in compiled] == ["/repro/engine/compile_duration"]


# --------------------------------------------------------------------------
# Device scopes: HLO metadata naming each engine call and micro-op kind
# --------------------------------------------------------------------------

def _strip_metadata(text: str) -> str:
    """HLO text without its metadata and source-location tables."""
    lines = [ln for ln in text.splitlines() if not re.match(
        r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", ln)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


def _scoped_program(mesh, collective, algorithm, segments, compression):
    eng = CollectiveEngine(mesh)
    kw = {"algorithm": algorithm, "segments": segments}
    if compression:
        kw["compression"] = compression

    def body(v):
        return getattr(eng, collective)(v[0], "x", **kw)[None]
    return eng.run(body, in_specs=P("x"), out_specs=P("x"))


@pytest.mark.parametrize("collective,algorithm,segments,codec,kind", [
    ("allgather", "ring", 1, None, "loop"),
    ("allgather", "ring", 4, None, "stream"),
    ("allgather", "recursive_doubling", 4, None, "chain"),
    ("allgather", "recursive_doubling", 1, None, "exchange"),
    ("alltoall", "bruck", 1, None, "rotate"),
    ("alltoall", "linear", 1, None, "stacked"),
    ("allreduce", "bidi_ring", 1, "int8", "codec"),
    ("allreduce", "ring", 4, None, "combine"),
])
def test_device_scopes_name_the_call_and_micro_op(
        mesh8, monkeypatch, collective, algorithm, segments, codec, kind):
    """Each engine call's ops carry `engine.<call>/algo.<algorithm>` and
    the micro-op kind that runs them in their op_name; the scopes are
    metadata, so the program and its results are those traced without
    them, bit for bit."""
    x = jnp.asarray(np.random.default_rng(1).integers(
        -8, 8, (8, 4096)).astype(np.float32))
    fn = _scoped_program(mesh8, collective, algorithm, segments, codec)
    text = fn.lower(x).compile().as_text()
    names = set("/".join(re.findall(r'op_name="([^"]*)"', text)).split("/"))
    assert {f"engine.{collective}", f"algo.{algorithm}",
            f"uop.{kind}"} <= names
    out = np.asarray(fn(x))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _scoped_program(mesh8, collective, algorithm, segments, codec)
    plain_text = plain.lower(x).compile().as_text()
    assert not re.search(r'op_name="[^"]*(engine|algo|uop)\.', plain_text)
    assert _strip_metadata(plain_text) == _strip_metadata(text)
    np.testing.assert_array_equal(np.asarray(plain(x)), out)


def test_native_calls_are_scoped_native(mesh8):
    eng = CollectiveEngine(mesh8, backend="native")
    fn = eng.run(lambda v: eng.allreduce(v, "x"), in_specs=P("x"),
                 out_specs=P("x"))
    text = fn.lower(jnp.ones((8, 128), jnp.float32)).compile().as_text()
    assert "engine.allreduce/algo.native" in text


def test_named_scope_is_reentrant():
    """A function that re-enters itself leaves no scope behind on what
    its caller traces next (`jax.named_scope`'s own decorator form
    does)."""
    @telemetry.named_scope("outer.rec")
    def rec(x, n):
        return rec(x, n - 1) + 1 if n else x * 2

    def f(x):
        return jnp.sin(rec(x, 2))
    text = jax.jit(f).lower(1.0).compile().as_text()
    sin = [ln for ln in text.splitlines() if "sine(" in ln]
    assert sin and "outer.rec" not in sin[0]
    assert "outer.rec" in text


# --------------------------------------------------------------------------
# Chrome trace-event schema validation
# --------------------------------------------------------------------------

def _validate_chrome_trace(doc):
    """Schema checks: pid/tid/ts present and monotone per track, every
    used track named by thread_name metadata, and control-plane spans
    well-nested per track (virtual-clock intervals are occupancy
    windows, which legitimately overlap)."""
    assert isinstance(doc["traceEvents"], list)
    assert all(e["ph"] != "C" for e in doc["traceEvents"])
    per_track = {}
    named = set()
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M")
        if ev["ph"] == "M":
            if ev["name"] == "thread_name":
                named.add((ev["pid"], ev["tid"]))
            continue
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["ts"], (int, float))
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        per_track.setdefault((ev["pid"], ev["tid"]), []).append(ev)
    assert set(per_track) <= named, "unnamed tracks in trace"
    for (pid, _tid), evs in per_track.items():
        last = None
        stack = []  # open span end times (well-nestedness check)
        for ev in evs:
            assert last is None or ev["ts"] >= last, \
                "timestamps not monotone within a track"
            last = ev["ts"]
            if ev["ph"] != "X" or pid != telemetry.CONTROL_PID:
                continue
            start, end = ev["ts"], ev["ts"] + ev["dur"]
            while stack and stack[-1] <= start:
                stack.pop()
            if stack:
                assert end <= stack[-1], "partially-overlapping spans"
            stack.append(end)


def test_control_plane_spans_validate_and_carry_margin(eng8):
    with telemetry.use(telemetry.Tracer()) as tr:
        sel = Selector()
        sel.choose("allreduce", 1 << 18, eng8.comm("x"))
        sel.choose("allreduce", 1 << 18, eng8.comm("x"))   # memoized
    doc = tr.to_chrome_trace()
    _validate_chrome_trace(doc)
    snap = tr.snapshot()
    assert snap["span.selector.choose.count"] == 1
    assert snap["instant.selector.cache_hit.count"] == 1
    assert snap["span.compile.count"] >= 1
    ev = next(e for e in doc["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "selector.choose")
    assert ev["args"]["candidates_priced"] > 1
    assert ev["args"]["algorithm"] and ev["args"]["protocol"]
    # the margin is winner-to-runner-up, never negative without tuning
    assert ev["args"]["margin_s"] is None or ev["args"]["margin_s"] >= 0.0


def test_compile_span_records_fusion_passes(eng8):
    from repro.core import program as program_mod
    sched = eng8._cached_schedule("allreduce", "ring",
                                  eng8.comm("x"), 0, "add")
    program_mod._COMPILE_CACHE.pop((sched, 4, None, True, True), None)
    with telemetry.use(telemetry.Tracer()) as tr:
        program_mod.compile_schedule(sched, segments=4)
        program_mod.compile_schedule(sched, segments=4)   # memoized now
    snap = tr.snapshot()
    assert snap["span.compile.count"] == 1
    assert snap["instant.compile.cache_hit.count"] == 1
    ev = next(e for e in tr.to_chrome_trace()["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "compile")
    passes = {p["pass"]: p for p in ev["args"]["passes"]}
    assert set(passes) == {"fuse_streams", "fuse_chains",
                           "fuse_stacked_recv"}
    assert passes["fuse_streams"]["ran"] is True
    assert passes["fuse_stacked_recv"] == {
        "pass": "fuse_stacked_recv", "ran": False, "reason": "segments > 1"}
    for rec in passes.values():
        if rec["ran"] and not rec["accepted"]:
            assert rec["reason"] == "no fusible run"
    assert ev["args"]["verify"] in ("off", "structural", "full")


def test_transport_retry_and_timeout_markers():
    with telemetry.use(telemetry.Tracer()) as tr:
        t = FaultyTransport(plan=FaultPlan(drops=frozenset({(0, 0, 1)})),
                            tier=TIERS["tcp-like"])
        t.deliver(0, 1)    # first attempt drops; the tier retransmits
    ev = next(e for e in tr.to_chrome_trace()["traceEvents"]
              if e.get("name") == "transport.retry")
    assert ev["args"] == {"src": 0, "dst": 1, "exchange": 0, "retries": 1,
                          "backoff_s": ev["args"]["backoff_s"],
                          "tier": "tcp-like"}
    assert ev["args"]["backoff_s"] > 0.0
    with telemetry.use(telemetry.Tracer()) as tr:
        t = FaultyTransport(plan=FaultPlan(drops=frozenset({(0, 0, 1)})),
                            tier=TIERS["udp-like"])   # no retries
        with pytest.raises(TransportTimeout):
            t.deliver(0, 1)
    assert tr.snapshot()["instant.transport.timeout.count"] == 1


# --------------------------------------------------------------------------
# MetricsRegistry + read-compatible .stats views
# --------------------------------------------------------------------------

def test_metrics_registry_counters_gauges_records():
    reg = telemetry.MetricsRegistry()
    reg.counter("n")
    view = reg.view()
    assert view == {"n": 0}            # mapping equality with plain dicts
    reg.inc("n")
    reg.inc("n", 2)
    assert view["n"] == 3              # views are live, not copies
    reg.set("g", 1.5)
    assert dict(view) == {"n": 3, "g": 1.5}
    view["g"] = 2.5                    # out-of-tree write-through shim
    assert reg.get("g") == 2.5
    assert reg.record(step=0, loss=1.0) == {"step": 0, "loss": 1.0}
    assert reg.records() == [{"step": 0, "loss": 1.0}]
    assert reg.snapshot() == {"n": 3, "g": 2.5}
    assert view.get("missing") is None and len(view) == 2


def test_component_stats_views_read_compatible(eng8):
    assert eng8.stats == {"gen_calls": 0, "sched_cache_hits": 0,
                          "selector.serial_wave_choices": 0,
                          "selector.streamed_choices": 0}
    assert eng8.selector.stats == {"choose_calls": 0, "cache_hits": 0,
                                   "gen_calls": 0}
    seq = Sequencer(eng8)
    assert seq.stats == {"issued": 0, "executed": 0,
                         "coalesced_buckets": 0, "coalesced_requests": 0}
    _fill(seq, "x", 1 << 16, n=2)
    assert seq.stats["issued"] == 2 and seq.metrics.get("issued") == 2
    seq.clear()


# --------------------------------------------------------------------------
# The timeline invariant: max interval end == mesh_makespan_s, bitwise
# --------------------------------------------------------------------------

def _max_end(tl):
    return max(iv["end_s"] for part in ("queues", "requests", "links")
               for iv in tl[part])


def test_timeline_bitwise_single_queue(eng8):
    seq = Sequencer(eng8)
    _fill(seq, "x", 1 << 20)
    mm = MeshMakespan.of(seq)
    tl = mm.timeline()
    assert _max_end(tl) == tl["end_s"] == mm.total() == seq.makespan("x")
    seq.clear()


def test_timeline_bitwise_shared_link(eng8):
    a, b = Sequencer(eng8), Sequencer(eng8)
    _fill(a, "x", 1 << 22, n=4)
    _fill(b, "x", 1 << 22, n=4)
    mm = MeshMakespan().add(a, "x").add(b, "x")
    tl = mm.timeline()
    assert _max_end(tl) == tl["end_s"] == mm.total()
    # shared-link serialization is visible: the ICI link track carries
    # both queues' wire windows back to back
    wire = [iv for iv in tl["links"] if iv["name"] == "wire"]
    assert len(wire) == 8
    a.clear()
    b.clear()


def test_timeline_bitwise_disjoint_fabrics(eng222):
    a, b = Sequencer(eng222), Sequencer(eng222)
    _fill(a, "data", 1 << 18, n=3)
    _fill(b, "model", 1 << 18, n=3)
    mm = MeshMakespan().add(a, "data").add(b, "model")
    tl = mm.timeline()
    assert _max_end(tl) == tl["end_s"] == mm.total()
    assert {iv["link"][:2][0] for iv in tl["links"]} == {"ici"}
    assert len({iv["track"] for iv in tl["links"]
                if iv["name"] == "wire"}) == 2   # two independent links
    a.clear()
    b.clear()


def test_timeline_bitwise_dep_chain(eng8):
    seq = Sequencer(eng8)
    r = seq.issue("reduce_scatter", np.zeros((1 << 18,), np.float32), "x")
    seq.issue("allgather", r, "x")
    mm = MeshMakespan.of(seq)
    tl = mm.timeline()
    assert _max_end(tl) == tl["end_s"] == mm.total() == seq.makespan("x")
    # the dependent request starts exactly at its dependency's chain end
    first, second = tl["requests"]
    assert second["start_s"] == first["end_s"] > 0.0
    seq.clear()


def test_timeline_bitwise_faulty_tier(eng8):
    env = PricingEnv(tier=TIERS["tcp-like"], drop_prob=0.1)
    seq = Sequencer(eng8)
    _fill(seq, "x", 1 << 18)
    mm = MeshMakespan.of(seq, env)
    tl = mm.timeline()
    assert _max_end(tl) == tl["end_s"] == mm.total() \
        == seq.makespan("x", env=env)
    seq.clear()


def test_timeline_ingest_exports_valid_trace(eng222):
    seq = Sequencer(eng222)
    r = seq.issue("reduce_scatter", np.zeros((1 << 16,), np.float32),
                  "data")
    seq.issue("allgather", r, "data")
    _fill(seq, "model", 1 << 16, n=2)
    tl = MeshMakespan.of(seq).timeline()
    tr = telemetry.Tracer()
    tr.ingest_timeline(tl)
    doc = tr.to_chrome_trace()
    _validate_chrome_trace(doc)
    names = {m["args"]["name"] for m in doc["traceEvents"]
             if m.get("ph") == "M" and m["name"] == "thread_name"}
    assert any(n.startswith("queue:") for n in names)
    assert any(n.startswith("link:") for n in names)
    seq.clear()


# --------------------------------------------------------------------------
# simulate_drain trace: validate + round-trip through trace_report.py
# --------------------------------------------------------------------------

def test_simulate_drain_trace_validates_and_round_trips(eng8, tmp_path):
    seq = Sequencer(eng8)
    with telemetry.use(telemetry.Tracer()) as tr:
        reqs = [seq.issue("allreduce", np.zeros((256,), np.float32), "x",
                          algorithm="ring") for _ in range(2)]
        seq.simulate_drain(
            _feeds(reqs, seed=3),
            fault_plan=FaultPlan(drops=frozenset({(0, 0, 1)})),
            tier=TIERS["tcp-like"])
    doc = tr.to_chrome_trace()
    _validate_chrome_trace(doc)
    names = [e.get("name") for e in doc["traceEvents"]]
    assert "request.issued" in names and "request.done" in names
    assert "transport.retry" in names    # the injected drop, recovered
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(doc))
    report = _load_script("trace_report")
    rep = report.summarize(report.load_events(str(path)))
    assert rep["virtual_end_s"] > 0.0
    assert rep["links"], "per-link utilization missing"
    assert all(0.0 < d["utilization"] <= 1.0 for d in rep["links"].values())
    assert len(rep["requests"]) == 2
    for r in rep["requests"]:
        assert r["status"] == "DONE"
        assert r["wire_s"] > 0.0 and r["lat_s"] > 0.0
        assert r["queue_wait_s"] >= 0.0 and r["dep_stall_s"] >= 0.0
    # second ring serialized behind the first: nonzero queue wait, and
    # offenders come back sorted by it
    assert rep["requests"][1]["queue_wait_s"] > 0.0
    waits = [r["queue_wait_s"] for r in rep["offenders"]]
    assert waits == sorted(waits, reverse=True)
    # the CLI itself runs on the same file (text and JSON modes)
    assert report.main([str(path), "--top", "3"]) == 0
    assert report.main([str(path), "--json"]) == 0


def test_simulate_drain_trace_attributes_dep_stall(eng8):
    seq = Sequencer(eng8)
    r = seq.issue("reduce_scatter", np.zeros((256,), np.float32), "x")
    seq.issue("allgather", r, "x")
    with telemetry.use(telemetry.Tracer()) as tr:
        seq.simulate_drain(_feeds([r], seed=5))
    reqs = [e for e in tr.to_chrome_trace()["traceEvents"]
            if e.get("ph") == "X" and e.get("name") == "request"]
    assert len(reqs) == 2
    dep = reqs[1]["args"]
    assert dep["dep_stall_s"] > 0.0       # waited on the reduce_scatter
    assert dep["queue_wait_s"] == 0.0     # dispatched as soon as ready
    assert dep["status"] == "DONE"


def test_simulate_drain_timeout_traced_as_terminal(eng8):
    seq = Sequencer(eng8)
    r = seq.issue("allreduce", np.zeros((1 << 20,), np.float32), "x",
                  timeout=1e-12)
    with telemetry.use(telemetry.Tracer()) as tr:
        seq.simulate_drain(_feeds([r], seed=6))
    assert r.status == Request.TIMED_OUT
    events = tr.to_chrome_trace()["traceEvents"]
    iv = next(e for e in events
              if e.get("ph") == "X" and e.get("name") == "request")
    assert iv["args"]["status"] == "TIMED_OUT"
    term = next(e for e in events if e.get("name") == "request.terminal")
    assert term["args"]["status"] == Request.TIMED_OUT


# --------------------------------------------------------------------------
# Read-only guarantee: tracing changes no priced or simulated bit
# --------------------------------------------------------------------------

def test_tracing_is_read_only_for_selection_and_pricing(eng8):
    comm = eng8.comm("x")
    base = Selector().choose("allreduce", 1 << 20, comm)
    with telemetry.use(telemetry.Tracer()):
        traced = Selector().choose("allreduce", 1 << 20, comm)
    assert traced.predicted_s == base.predicted_s
    assert (traced.algorithm, traced.protocol, traced.segments) \
        == (base.algorithm, base.protocol, base.segments)

    seq = Sequencer(eng8)
    _fill(seq, "x", 1 << 20)
    ref_makespan = seq.makespan("x")
    ref_report = MeshMakespan.of(seq).report()
    with telemetry.use(telemetry.Tracer()):
        assert seq.makespan("x") == ref_makespan
        assert MeshMakespan.of(seq).report() == ref_report
    seq.clear()


def test_tracing_is_read_only_across_the_priced_surface(eng8, eng222):
    """The wider pricing surface under an enabled tracer: every segment
    count of a streamed program, a hierarchical pick over a DCN product,
    a makespan under a lossy reliability tier, and two queues composed
    across disjoint fabrics all price bitwise-equal, and the tracer saw
    the selection and composition (the instrumentation is live)."""
    comm = eng8.comm("x")
    product = Communicator(axis="pod", size=16, is_dcn=True).factor(4)
    tier = PricingEnv(comm=comm, tier=TIERS["tcp-like"], drop_prob=0.05)

    def price():
        ring = A.ring_allreduce(comm)
        ladder = [ring.compile(segments=k).cost(16 << 20, comm)
                  for k in Selector.DEFAULT_SEGMENT_CANDIDATES]
        hier = Selector().choose("allreduce", 4 << 20, product)
        seq = Sequencer(eng8)
        _fill(seq, "x", 1 << 20)
        lossy = seq.makespan("x", env=tier)
        seq.clear()
        mm, seqs = MeshMakespan(), []
        for axis in ("data", "pod"):
            s = Sequencer(eng222)
            _fill(s, axis, 1 << 20)
            mm.add(s, axis)
            seqs.append(s)
        total = mm.total()
        for s in seqs:
            s.clear()
        return (ladder, hier.predicted_s, hier.algorithm, hier.segments,
                lossy, total)

    ref = price()
    with telemetry.use(telemetry.Tracer()) as tr:
        traced = price()
    assert traced == ref
    assert tr._events, "tracer recorded nothing: instrumentation gone?"


def test_tracing_is_read_only_for_simulate_drain(eng8):
    def run():
        seq = Sequencer(eng8)
        reqs = [seq.issue("allreduce", np.zeros((128,), np.float32), "x",
                          algorithm="ring") for _ in range(2)]
        return reqs, seq.simulate_drain(_feeds(reqs, seed=7))

    ref_reqs, ref = run()
    with telemetry.use(telemetry.Tracer()):
        reqs, out = run()
    for rr, r in zip(ref_reqs, reqs):
        for a, b in zip(ref[rr], out[r]):
            np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------
# Trainer._queue_stats: both paths are explicit
# --------------------------------------------------------------------------

def _trainer_queue_stats(engine):
    from repro.runtime.trainer import Trainer
    stub = types.SimpleNamespace(ts=types.SimpleNamespace(
        ctx=types.SimpleNamespace(engine=engine)))
    return Trainer._queue_stats(stub)


def test_trainer_queue_stats_no_queue_is_explicit_none(eng8):
    assert eng8._queue is None
    assert _trainer_queue_stats(eng8) == {
        "queue_issued": None, "queue_coalesced": None,
        "grad_sync_makespan_s": None}


def test_trainer_queue_stats_with_live_queue(eng8):
    _fill(eng8.queue, "x", 1 << 16, n=2)
    eng8.metrics.set("grad_sync_makespan_s", 1.25)
    assert _trainer_queue_stats(eng8) == {
        "queue_issued": 2, "queue_coalesced": 0,
        "grad_sync_makespan_s": 1.25}
    eng8.queue.clear()
