"""The collective offload sequencer (core/sequencer.py): non-blocking
requests, per-communicator FIFO + dependency edges, coalescing, and the
queue-level makespan model — the CCLO request-queue subsystem."""
import types

import numpy as np
import pytest

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import (
    CollectiveEngine, Communicator, Schedule, Sel, Selector, Step,
    register_collective, unregister_collective, simulator,
)
from repro.core.sequencer import Sequencer
from hypothesis import given, settings, strategies as st


@pytest.fixture(scope="module")
def engines(mesh8):
    return CollectiveEngine(mesh8, backend="microcode")


# --------------------------------------------------------------------------
# Bitwise parity: issued == blocking, out-of-order wait() and drain()
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.int8])
def test_issued_collectives_bitwise_equal_blocking(engines, rng, dtype):
    """Every built-in collective issued through the queue equals its
    blocking counterpart bit-for-bit, with waits out of FIFO order and
    the stragglers left to drain()."""
    eng = engines

    def queued(a, b, c, d, e, f, h):
        r1 = eng.iallreduce(a, "x")
        r2 = eng.ireduce_scatter(b, "x")
        r3 = eng.iallgather(c, "x")
        r4 = eng.ibcast(d, "x", root=2)
        r5 = eng.ialltoall(e, "x")
        r6 = eng.ireduce(f, "x", op="max")
        r7 = eng.issue("gather", h, "x", root=1)
        out3, out1 = r3.wait(), r1.wait()   # out of issue order
        eng.queue.drain("x")                # the stragglers via drain
        return (out1, r2.result, out3, r4.result, r5.result, r6.result,
                r7.result)

    def blocking(a, b, c, d, e, f, h):
        return (eng.allreduce(a, "x"), eng.reduce_scatter(b, "x"),
                eng.allgather(c, "x"), eng.bcast(d, "x", root=2),
                eng.alltoall(e, "x"), eng.reduce(f, "x", op="max"),
                eng.gather(h, "x", root=1))

    def draw(shape):
        return jnp.asarray(
            rng.integers(-40, 40, size=shape).astype(dtype))

    args = (draw((8, 48)), draw((8, 64)), draw((8, 16)), draw((8, 24)),
            draw((64, 6)), draw((8, 32)), draw((8, 12)))
    specs = (P("x"),) * 7
    outs = (P(), P("x"), P("x"), P(), P("x"), P(), P("x"))
    got = eng.run(queued, in_specs=specs, out_specs=outs)(*args)
    want = eng.run(blocking, in_specs=specs, out_specs=outs)(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _linear_scatter(comm, root: int = 0) -> Schedule:
    n = comm.size
    steps = tuple(
        Step(perm=((root, (root + i + 1) % n),), op="copy",
             send_sel=Sel.chunk(lambda r, s, i=i: (root + i + 1) % n),
             recv_sel=Sel.chunk(lambda r, s, i=i: (root + i + 1) % n),
             bytes_frac=1.0 / n, mask_recv=True)
        for i in range(n - 1))
    return Schedule(name="linear", collective="qscatter", nranks=n,
                    steps=steps, chunks=n, result="shard",
                    owned_chunk=lambda r: r, relay="original")


@pytest.mark.parametrize("dtype", [np.float32, np.int8])
def test_issued_plugin_collective_bitwise_equal_blocking(engines, rng,
                                                         dtype):
    """Out-of-tree (plugin-registered) collectives ride the queue like
    built-ins: icollective == blocking collective, bit-for-bit."""
    eng = engines
    register_collective("qscatter", _linear_scatter, algorithm="linear")
    try:
        def queued(s):
            r = eng.icollective("qscatter", s, "x", algorithm="linear")
            return r.wait()

        def blocking(s):
            return eng.collective("qscatter", s, "x", algorithm="linear")

        data = jnp.asarray(
            rng.integers(-40, 40, size=(8, 16)).astype(dtype))
        got = eng.run(queued, in_specs=P("x"), out_specs=P("x"))(data)
        want = eng.run(blocking, in_specs=P("x"), out_specs=P("x"))(data)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    finally:
        unregister_collective("qscatter")


def test_coalesced_queue_bitwise_equal_blocking_in_engine(engines, rng):
    """Small same-(op, dtype) reductions coalesce into ONE bucketed
    program inside a traced drain — and still match the blocking calls
    bit-for-bit (the ORDER_SAFE eligibility rule)."""
    eng = engines
    before = eng.queue.stats["coalesced_buckets"]

    def queued(a, b, c):
        rs = [eng.iallreduce(v, "x", algorithm="recursive_doubling")
              for v in (a, b, c)]
        return rs[2].wait(), rs[0].wait(), rs[1].wait()

    def blocking(a, b, c):
        o = [eng.allreduce(v, "x", algorithm="recursive_doubling")
             for v in (a, b, c)]
        return o[2], o[0], o[1]

    args = tuple(jnp.asarray(rng.normal(size=(8, n)), jnp.float32)
                 for n in (40, 8, 24))
    specs = (P("x"),) * 3
    got = eng.run(queued, in_specs=specs, out_specs=(P(),) * 3)(*args)
    want = eng.run(blocking, in_specs=specs, out_specs=(P(),) * 3)(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert eng.queue.stats["coalesced_buckets"] > before


def test_itree_allreduce_matches_blocking(mesh222, rng):
    """The trainer's queued gradient path (issue-all-then-wait tickets)
    is bitwise-identical to the blocking tree_allreduce."""
    eng = CollectiveEngine(mesh222)
    tree = {"w": jnp.asarray(rng.normal(size=(2, 2, 2, 6)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(2, 2, 2, 3)), jnp.float32)}
    spec = {"w": P("pod", "data", "model"), "b": P("pod", "data", "model")}

    got = eng.run(lambda t: eng.itree_allreduce(t, ("data", "pod")).wait(),
                  in_specs=(spec,), out_specs=spec)(tree)
    want = eng.run(lambda t: eng.tree_allreduce(t, ("data", "pod")),
                   in_specs=(spec,), out_specs=spec)(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


# --------------------------------------------------------------------------
# FIFO + dependency ordering (property test)
# --------------------------------------------------------------------------

class _FakeEngine:
    """Duck-typed engine that records drain order instead of executing;
    enough surface for the sequencer (comm sizes, selector, methods)."""

    backend = "microcode"

    def __init__(self, axes):
        self.mesh = types.SimpleNamespace(shape=dict(axes))
        self.selector = Selector()
        self.log = []

    def comm(self, axis):
        return Communicator(axis=axis, size=self.mesh.shape[axis])

    def _run(self, x, axis, **_kw):
        return np.asarray(x)

    allreduce = reduce_scatter = allgather = bcast = reduce = _run
    gather = alltoall = _run

    def collective(self, name, x, axis, **_kw):
        return np.asarray(x)


class _TracingSequencer(Sequencer):
    """Records the order requests complete (deps recurse inside
    `_run_item`, so completion order IS execution order)."""

    def __init__(self, engine, **kw):
        super().__init__(engine, **kw)
        self.order = []

    def _finish(self, r, result):
        super()._finish(r, result)
        self.order.append(r)


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_fifo_and_dependency_order_never_violated(data):
    """Property: whatever the wait order, (a) requests on one
    communicator execute in issue order (FIFO), (b) every dependency —
    inferred from buffer identity, explicit `after=`, or a Request
    operand — executes before its dependent."""
    eng = _FakeEngine({"x": 8, "y": 4})
    seq = _TracingSequencer(eng, coalesce_bytes=0)  # ordering only
    reqs = []
    n_req = data.draw(st.integers(min_value=2, max_value=10))
    arrays = []
    for _ in range(n_req):
        axis = ("x", "y")[data.draw(st.integers(0, 1))]
        kind = data.draw(st.integers(0, 3)) if reqs else 0
        after = None
        if kind == 1 and arrays:  # same-buffer conflict
            x = arrays[data.draw(st.integers(0, len(arrays) - 1))]
        elif kind == 2:           # request-operand chaining
            x = reqs[data.draw(st.integers(0, len(reqs) - 1))]
        else:
            x = np.zeros((data.draw(st.integers(1, 8)) * 8,), np.float32)
            arrays.append(x)
            if kind == 3:         # explicit after= edge
                after = (reqs[data.draw(st.integers(0, len(reqs) - 1))],)
        reqs.append(seq.issue("allreduce", x, axis, after=after))
    # wait a random subset in a random order, then drain the rest
    n_waits = data.draw(st.integers(0, n_req))
    for _ in range(n_waits):
        reqs[data.draw(st.integers(0, n_req - 1))].wait()
    seq.drain()

    assert len(seq.order) == n_req
    done_at = {r: i for i, r in enumerate(seq.order)}
    for axis in ("x", "y"):
        issued = [r for r in reqs if r.axis == axis]
        executed = sorted(issued, key=lambda r: done_at[r])
        assert executed == issued  # per-communicator FIFO
    for r in reqs:
        for d in r.deps:
            assert done_at[d] < done_at[r]
        if isinstance(r.operand, type(reqs[0])):
            assert done_at[r.operand] < done_at[r]


# --------------------------------------------------------------------------
# Coalescing (property test): bitwise-equal to uncoalesced issues
# --------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(data=st.data())
def test_coalesced_buckets_bitwise_equal_uncoalesced(engines, data):
    """Property: a coalesced bucket's per-request results are bitwise
    identical to issuing each request alone — for fp32 (non-associative
    adds: only true because the bucket algorithm's elementwise combine
    order is position-independent) and int8 (wrapping adds)."""
    eng = engines
    n = 8
    dtype = (np.float32, np.int8)[data.draw(st.integers(0, 1))]
    op = ("add", "max")[data.draw(st.integers(0, 1))]
    m = data.draw(st.integers(2, 4))
    sizes = [data.draw(st.integers(1, 40)) for _ in range(m)]
    seed = data.draw(st.integers(0, 1 << 16))
    prng = np.random.default_rng(seed)

    seq = Sequencer(eng)
    feeds, reqs = {}, []
    for sz in sizes:
        x = np.zeros((sz,), dtype)
        r = seq.issue("allreduce", x, "x", op=op,
                      algorithm="recursive_doubling")
        feeds[r] = [prng.integers(-50, 50, size=(sz,)).astype(dtype)
                    for _ in range(n)]
        reqs.append(r)
    plan = seq.plan("x")
    assert len(plan) == 1 and plan[0].coalesced  # the bucket formed
    got = seq.simulate_drain(feeds)

    comm = eng.comm("x")
    sched = eng._cached_schedule("allreduce", "recursive_doubling",
                                 comm, 0, op)
    prog = sched.compile()
    for r in reqs:
        want = simulator.run_collective("allreduce", sched, prog,
                                        feeds[r])
        for rank in range(n):
            np.testing.assert_array_equal(got[r][rank], want[rank])


def test_conflicting_requests_do_not_coalesce(engines, rng):
    """Same-buffer conflicts carry a dependency edge, which excludes the
    dependent request from any bucket (members must be independent)."""
    eng = engines
    seq = Sequencer(eng)
    x = np.zeros((16,), np.float32)
    r1 = seq.issue("allreduce", x, "x", algorithm="recursive_doubling")
    r2 = seq.issue("allreduce", x, "x", algorithm="recursive_doubling")
    assert r2.deps == (r1,)
    plan = seq.plan("x")
    assert all(not it.coalesced for it in plan)
    seq.clear()


def test_large_or_mixed_requests_do_not_coalesce(engines):
    eng = engines
    seq = Sequencer(eng)
    seq.issue("allreduce", np.zeros((1 << 18,), np.float32), "x")
    seq.issue("allreduce", np.zeros((1 << 18,), np.float32), "x")
    assert all(not it.coalesced for it in seq.plan("x"))  # > cap
    seq.clear()
    seq.issue("allreduce", np.zeros((16,), np.float32), "x")
    seq.issue("allreduce", np.zeros((16,), np.int8), "x")
    assert all(not it.coalesced for it in seq.plan("x"))  # dtype split
    seq.clear()
    # ring is NOT order-safe (per-chunk combine order): explicit rings
    # never bucket even when tiny
    seq.issue("allreduce", np.zeros((16,), np.float32), "x",
              algorithm="ring")
    seq.issue("allreduce", np.zeros((16,), np.float32), "x",
              algorithm="ring")
    assert all(not it.coalesced for it in seq.plan("x"))
    seq.clear()


# --------------------------------------------------------------------------
# Makespan: the queue-level pricing model
# --------------------------------------------------------------------------

def test_cost_terms_decomposes_cost(engines):
    """Program.cost_terms is an exact split of Program.cost (latency
    half + wire half) for every algorithm/segment shape the queue
    prices."""
    comm = Communicator(axis="x", size=8)
    sel = Selector()
    for coll, nbytes in (("allreduce", 1 << 20), ("allreduce", 4096),
                         ("reduce_scatter", 1 << 22),
                         ("allgather", 1 << 16)):
        choice = sel.choose(coll, nbytes, comm)
        prog = choice.program
        lat, wire = prog.cost_terms(nbytes, comm)
        assert lat > 0 and wire > 0
        assert lat + wire == pytest.approx(prog.cost(nbytes, comm),
                                           rel=1e-12)


@pytest.mark.parametrize("m", [2, 4, 8])
def test_makespan_of_independent_queue_strictly_below_serial(engines,
                                                             rng, m):
    """Acceptance: a queue of m > 1 independent same-axis collectives
    prices strictly below the sum of blocking Program.costs, and the
    simulator-executed drain is bitwise-equal to the blocking sequence."""
    eng = engines
    n = 8
    seq = Sequencer(eng)
    feeds, reqs = {}, []
    for _ in range(m):
        x = np.zeros((1 << 16,), np.float32)  # > coalesce cap: no bucket
        r = seq.issue("allreduce", x, "x")
        feeds[r] = [rng.normal(size=(1 << 16,)).astype(np.float32)
                    for _ in range(n)]
        reqs.append(r)
    assert all(not it.coalesced for it in seq.plan("x"))
    comm = eng.comm("x")
    makespan = seq.makespan("x")
    serial = seq.serial_cost("x")
    # the serial reference really is the sum of blocking Program.costs
    choice = eng.selector.choose("allreduce", 4 << 16, comm, elem_bytes=4)
    assert serial == pytest.approx(
        m * choice.program.cost(4 << 16, comm), rel=1e-12)
    assert makespan < serial
    assert makespan >= choice.program.cost(4 << 16, comm)  # >= one call

    got = seq.simulate_drain(feeds)
    sched, prog = choice.schedule, choice.program
    for r in reqs:
        want = simulator.run_collective("allreduce", sched, prog,
                                        feeds[r])
        for rank in range(n):
            np.testing.assert_array_equal(got[r][rank], want[rank])


def test_makespan_dependency_chain_gets_no_credit(engines):
    """A fully serial chain (each request consuming the previous one's
    result) prices as the sum of full costs — the queue model never
    grants overlap a dependency forbids."""
    eng = engines
    seq = Sequencer(eng)
    r = seq.issue("allreduce", np.zeros((1 << 16,), np.float32), "x")
    for _ in range(3):
        r = seq.issue("allreduce", r, "x")
    assert seq.makespan("x") == pytest.approx(seq.serial_cost("x"),
                                              rel=1e-9)
    seq.clear()


def test_after_override_never_drops_dataflow_edges(engines):
    """Regression: `after=` overrides the buffer-identity inference
    only — a Request operand is a structural dataflow edge the drain
    must serialize, so the makespan may not price it away."""
    eng = engines
    seq = Sequencer(eng)
    r1 = seq.issue("allreduce", np.zeros((1 << 18,), np.float32), "x")
    r2 = seq.issue("allreduce", r1, "x", after=[])
    assert r1 in r2.deps
    assert seq.makespan("x") == pytest.approx(seq.serial_cost("x"),
                                              rel=1e-9)
    seq.clear()


def test_makespan_coalesced_bucket_prices_one_program(engines):
    """Tiny requests coalesce: the queue's makespan equals ONE bucketed
    program's cost, far below the m-alpha serial sum."""
    eng = engines
    seq = Sequencer(eng)
    for _ in range(6):
        seq.issue("allreduce", np.zeros((64,), np.float32), "x")
    plan = seq.plan("x")
    assert len(plan) == 1 and plan[0].coalesced
    comm = eng.comm("x")
    choice = eng.selector.choose("allreduce", 6 * 64 * 4, comm,
                                 elem_bytes=4)
    assert seq.makespan("x") == pytest.approx(
        choice.program.cost(6 * 64 * 4, comm), rel=1e-12)
    assert seq.makespan("x") < seq.serial_cost("x")
    seq.clear()


def test_empty_and_single_request_makespan(engines):
    eng = engines
    seq = Sequencer(eng)
    assert seq.makespan("x") == 0.0
    for elems in (1 << 16, 1 << 10):  # above and below the coalesce cap
        seq.issue("allreduce", np.zeros((elems,), np.float32), "x")
        assert not any(it.coalesced for it in seq.plan("x"))
        assert seq.makespan("x") == pytest.approx(seq.serial_cost("x"),
                                                  rel=1e-9)
        seq.clear()


def test_simulate_drain_honours_op_and_root_under_auto(engines, rng):
    """Regression: an auto-algorithm request with op='max' (or a nonzero
    root) must simulate the schedule REBUILT for that op/root — not the
    selector's op='add'/root=0 pricing schedule (the engine drain always
    did this via _resolve; the simulator path must match)."""
    eng = engines
    n = 8
    seq = Sequencer(eng)
    x = np.zeros((32,), np.float32)
    r = seq.issue("allreduce", x, "x", op="max")
    feeds = {r: [rng.normal(size=(32,)).astype(np.float32)
                 for _ in range(n)]}
    got = seq.simulate_drain(feeds)
    want = np.max(np.stack(feeds[r]), axis=0)
    for rank in range(n):
        np.testing.assert_allclose(got[r][rank], want, rtol=1e-6)

    seq2 = Sequencer(eng)
    y = np.zeros((24,), np.float32)
    r2 = seq2.issue("bcast", y, "x", root=3)
    feeds2 = {r2: [rng.normal(size=(24,)).astype(np.float32)
                   for _ in range(n)]}
    got2 = seq2.simulate_drain(feeds2)
    for rank in range(n):
        np.testing.assert_array_equal(got2[r2][rank], feeds2[r2][3])


def test_issue_records_static_result_shapes(engines):
    eng = engines
    seq = Sequencer(eng)
    r1 = seq.issue("reduce_scatter", np.zeros((64,), np.float32), "x")
    assert r1.shape == (8,)
    r2 = seq.issue("allgather", r1, "x")
    assert r2.shape == (64,)
    assert r2.deps == (r1,)
    with pytest.raises(ValueError):
        _ = r2.result  # not materialized yet
    seq.clear()
