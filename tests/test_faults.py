"""Fault-tolerant transport (core/faults.py + sequencer/simulator
integration): deterministic fault plans, reliability tiers, typed
terminal states, abort cleanup (the PR 5 watch item), the alltoall
leading-dim clamp, degraded-communicator replanning, and the chaos
invariant — every request under every fault schedule ends bitwise-equal
to the fault-free run or in a typed terminal state, never a hang."""
import os

import numpy as np
import pytest

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import (
    CollectiveEngine, Communicator, FaultPlan, Request, RequestCancelled,
    Selector, Sequencer, TIERS,
)
from repro.core.faults import (
    PeerFailedError, ReliabilityTier, TransportTimeout,
)
from repro.core.hw_spec import ACCL_CLUSTER
from repro.core.program import fit_segments
from hypothesis import given, settings, strategies as st


@pytest.fixture(scope="module")
def eng8(mesh8):
    return CollectiveEngine(mesh8, backend="microcode")


def _feeds(reqs, seed, n=8):
    """Deterministic per-rank integer-valued feeds for leaf requests
    (integer-valued so int8 sums are exact modulo wraparound and fp32
    sums are exact, making bitwise comparisons meaningful)."""
    rng = np.random.default_rng(seed)
    return {r: [rng.integers(-20, 20, size=r.operand.shape)
                .astype(r.dtype) for _ in range(n)]
            for r in reqs if not isinstance(r.operand, Request)}


# --------------------------------------------------------------------------
# Backoff / tier determinism (no wall-clock anywhere in the model)
# --------------------------------------------------------------------------

def test_backoff_schedule_deterministic():
    tier = TIERS["tcp-like"]
    sched = tier.backoff_schedule()
    assert sched == tier.backoff_schedule()  # pure function of the tier
    assert sched == (2e-6, 4e-6, 8e-6, 1.6e-5, 3.2e-5)
    assert tier.backoff(0) == 0.0
    # the cap binds eventually
    capped = ReliabilityTier("t", max_retries=30, backoff_base=1e-6,
                             backoff_cap=1e-4)
    assert capped.backoff_schedule()[-1] == 1e-4
    assert max(capped.backoff_schedule()) == 1e-4


def test_expected_transmissions_truncated_geometric():
    udp, tcp = TIERS["udp-like"], TIERS["tcp-like"]
    assert udp.expected_transmissions(0.0) == 1.0
    assert udp.expected_transmissions(0.7) == 1.0  # one shot, no retry
    assert tcp.expected_transmissions(0.0) == 1.0
    assert tcp.expected_transmissions(0.5) == pytest.approx(
        (1 - 0.5 ** 6) / 0.5)
    assert tcp.expected_backoff(0.0) == 0.0
    assert tcp.expected_backoff(0.5) > 0.0


def test_fault_plan_drop_decisions_order_independent():
    plan = FaultPlan(seed=7, drop_prob=0.3)
    coords = [(x, s, d, a) for x in range(4) for s in range(4)
              for d in range(4) for a in range(2)]
    fwd = [plan.drops_segment(*c) for c in coords]
    rev = [plan.drops_segment(*c) for c in reversed(coords)]
    assert fwd == list(reversed(rev))      # order-independent
    assert fwd == [FaultPlan(seed=7, drop_prob=0.3).drops_segment(*c)
                   for c in coords]        # plan-identity-independent
    assert any(fwd) and not all(fwd)
    # retries re-roll: some first-attempt drop succeeds on attempt 1
    assert any(plan.drops_segment(x, s, d, 0)
               and not plan.drops_segment(x, s, d, 1)
               for x in range(8) for s in range(4) for d in range(4))


def test_fault_plan_flaps_and_dead():
    plan = FaultPlan(flaps=((0, 1, 2, 5),), dead=((3, 4),))
    assert not plan.link_flapped(0, 1, 1)
    assert plan.link_flapped(0, 1, 2) and plan.link_flapped(0, 1, 4)
    assert not plan.link_flapped(0, 1, 5)      # end exclusive
    assert not plan.link_flapped(1, 0, 3)      # directional
    assert plan.dead_at(3) == frozenset()
    assert plan.dead_at(4) == {3} == plan.dead_at(9)


# --------------------------------------------------------------------------
# Typed terminal states in the simulated drain
# --------------------------------------------------------------------------

def test_tcp_tier_recovers_bitwise_from_explicit_drop(eng8):
    xs = [np.zeros((64,), np.float32) for _ in range(2)]
    ref_seq = Sequencer(eng8)
    ref = [ref_seq.issue("allreduce", x, "x", algorithm="ring") for x in xs]
    ref_out = ref_seq.simulate_drain(_feeds(ref, seed=11))

    seq = Sequencer(eng8)
    reqs = [seq.issue("allreduce", x, "x", algorithm="ring") for x in xs]
    # drop the first attempt of one segment; the tcp tier retransmits
    out = seq.simulate_drain(
        _feeds(reqs, seed=11),
        fault_plan=FaultPlan(drops=frozenset({(0, 0, 1), (3, 2, 3)})),
        tier=TIERS["tcp-like"])
    for r_ref, r in zip(ref, reqs):
        assert r.status == Request.DONE
        for a, b in zip(ref_out[r_ref], out[r]):
            np.testing.assert_array_equal(a, b)


def test_udp_tier_loss_is_typed_timeout_not_hang(eng8):
    seq = Sequencer(eng8)
    r = seq.issue("allreduce", np.zeros((64,), np.float32), "x",
                  algorithm="ring")
    seq.simulate_drain(_feeds([r], seed=0),
                       fault_plan=FaultPlan(drops=frozenset({(0, 0, 1)})),
                       tier=TIERS["udp-like"])
    assert r.status == Request.TIMED_OUT
    assert isinstance(r.error, TransportTimeout)
    with pytest.raises(TransportTimeout):
        r.wait()
    assert seq.outstanding() == []  # no hang, nothing stuck in the queue


def test_dead_rank_is_peer_failed_and_cascades_cancel(eng8):
    seq = Sequencer(eng8)
    r1 = seq.issue("allreduce", np.zeros((64,), np.float32), "x",
                   algorithm="ring")
    r2 = seq.issue("allreduce", r1, "x", algorithm="ring")  # depends on r1
    seq.simulate_drain(_feeds([r1], seed=1),
                       fault_plan=FaultPlan(dead=((2, 0),)),
                       tier=TIERS["tcp-like"])
    assert r1.status == Request.PEER_FAILED
    assert isinstance(r1.error, PeerFailedError) and r1.error.rank == 2
    assert r2.status == Request.CANCELLED
    with pytest.raises(RequestCancelled):
        r2.wait()
    assert seq.outstanding() == []


def test_virtual_timeout_deterministic_no_wallclock(eng8):
    # the virtual clock is the priced program cost: a deadline below it
    # times out, one above it succeeds — identical on every run, because
    # no wall-clock is consulted anywhere in the simulated path
    for _ in range(2):
        seq = Sequencer(eng8)
        fast = seq.issue("allreduce", np.zeros((64,), np.float32), "x",
                         algorithm="ring", timeout=1.0)
        slow = seq.issue("allreduce", np.zeros((64,), np.float32), "x",
                         algorithm="ring", timeout=1e-12)
        seq.simulate_drain(_feeds([fast, slow], seed=2))
        assert fast.status == Request.DONE
        assert slow.status == Request.TIMED_OUT
        assert isinstance(slow.error, TransportTimeout)


def test_cancel_request_and_dependents(eng8):
    seq = Sequencer(eng8)
    r1 = seq.issue("allreduce", np.zeros((8,), np.float32), "x")
    r2 = seq.issue("allreduce", r1, "x")
    r3 = seq.issue("allreduce", np.zeros((8,), np.float32), "x")
    r1.cancel()
    assert r1.status == Request.CANCELLED
    assert r2.status == Request.CANCELLED  # dataflow dependent cascades
    assert r3.status == Request.PENDING    # independent request untouched
    r1.cancel()                            # idempotent
    assert seq.outstanding() == [r3]


# --------------------------------------------------------------------------
# PR 5 watch item: abort provably empties engine.queue
# --------------------------------------------------------------------------

def test_abort_mid_drain_leaves_engine_queue_empty(eng8, rng):
    eng = eng8

    def traced(a, b):
        r1 = eng.iallreduce(a, "x", algorithm="ring")
        eng.iallreduce(b, "x", algorithm="ring")  # never waited
        out = r1.wait()
        dropped = eng.queue.abort()  # abandon the rest mid-drain
        assert len(dropped) == 1
        return out

    a = jnp.asarray(rng.standard_normal((8, 32)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((8, 32)).astype(np.float32))
    got = eng.run(traced, in_specs=(P("x"), P("x")), out_specs=P())(a, b)
    # the queue is empty: no request, no buffer-identity entry, hence no
    # stale TRACER can leak out of the abandoned trace
    assert eng.queue.outstanding() == []
    assert eng.queue._buffer_owner == {}
    # and the next collective (a fresh trace) is unaffected
    want = eng.run(lambda x: eng.allreduce(x, "x", algorithm="ring"),
                   in_specs=P("x"), out_specs=P())(a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_context_manager_aborts_leftovers(eng8):
    with Sequencer(eng8) as seq:
        r1 = seq.issue("allreduce", np.zeros((16,), np.float32), "x")
        r2 = seq.issue("allreduce", r1, "x")
    assert r1.status == Request.CANCELLED
    assert r2.status == Request.CANCELLED
    assert seq.outstanding() == [] and seq._buffer_owner == {}
    with pytest.raises(RequestCancelled):
        r1.wait()


def test_context_manager_aborts_on_exception_mid_drain(eng8):
    with pytest.raises(RuntimeError, match="boom"):
        with Sequencer(eng8) as seq:
            seq.issue("allreduce", np.zeros((16,), np.float32), "x")
            raise RuntimeError("boom")
    assert seq.outstanding() == []


# --------------------------------------------------------------------------
# Graceful degradation: shrink the communicator, replan, continue
# --------------------------------------------------------------------------

def test_communicator_shrink_helpers():
    comm = Communicator(axis="x", size=8)
    assert comm.shrunk(7).size == 7
    assert comm.shrunk(7).axis == comm.axis
    assert comm.without_ranks({3}).size == 7
    assert comm.without_ranks({3, 5}).size == 6
    with pytest.raises(ValueError):
        comm.shrunk(0)
    with pytest.raises(ValueError):
        comm.without_ranks({11})
    # rank-id-aware remap: mid-mesh survivors keep their GLOBAL ids,
    # and repeated failures compose through the rank table
    assert comm.global_ranks == tuple(range(8))
    d = comm.without_ranks({3, 5})
    assert d.global_ranks == (0, 1, 2, 4, 6, 7)
    assert d.without_ranks({0}).global_ranks == (1, 2, 4, 6, 7)
    with pytest.raises(ValueError):
        d.without_ranks({6})  # local ids index the CURRENT group (0..5)


def test_dead_rank_shrinks_communicator_and_replans(eng8):
    """The dead-rank grad-sync scenario at queue level: the request in
    flight when the rank dies ends PEER_FAILED, the communicator shrinks
    to the 7 survivors, the selector replans the still-queued collectives
    on the degraded fabric, and they complete with survivor-exact sums."""
    xs = [np.zeros((64,), np.float32) for _ in range(3)]
    seq = Sequencer(eng8)
    reqs = [seq.issue("allreduce", x, "x", algorithm="ring") for x in xs]
    feeds = _feeds(reqs, seed=5)
    out = seq.simulate_drain(feeds, fault_plan=FaultPlan(dead=((3, 2),)),
                             tier=TIERS["tcp-like"], degrade=True)
    assert reqs[0].status == Request.PEER_FAILED
    survivors = [r for r in range(8) if r != 3]
    for req in reqs[1:]:
        assert req.status == Request.DONE
        per = out[req]
        assert len(per) == 7  # executed on the shrunk communicator
        want = np.sum([feeds[req][r] for r in survivors], axis=0)
        for got in per:
            np.testing.assert_allclose(got, want, rtol=1e-6)
    assert seq.outstanding() == []


# --------------------------------------------------------------------------
# Honest retransmission pricing
# --------------------------------------------------------------------------

def test_tier_pricing_neutral_by_default_and_monotone(eng8):
    comm = eng8.comm("x")
    sched = eng8._cached_schedule("allreduce", "ring", comm, 0, "add")
    prog = sched.compile()
    nbytes = 1 << 16
    base = prog.cost(nbytes, comm)
    assert prog.cost(nbytes, comm, tier=None) == base  # bitwise-neutral
    assert prog.cost(nbytes, comm, tier=TIERS["tcp-like"],
                     drop_prob=0.0) == base            # lossless: no charge
    lossy = prog.cost(nbytes, comm, tier=TIERS["tcp-like"], drop_prob=0.2)
    lossier = prog.cost(nbytes, comm, tier=TIERS["tcp-like"], drop_prob=0.5)
    assert base < lossy < lossier
    lat, wire = prog.cost_terms(nbytes, comm, tier=TIERS["tcp-like"],
                                drop_prob=0.2)
    assert lat + wire == pytest.approx(lossy)


def test_makespan_reflects_reliability_tier(eng8):
    seq = Sequencer(eng8)
    for _ in range(4):
        seq.issue("allreduce", np.zeros((1024,), np.float32), "x",
                  algorithm="ring")
    base = seq.makespan("x")
    priced = seq.makespan("x", tier=TIERS["tcp-like"], drop_prob=0.1)
    assert priced > base
    assert seq.makespan("x", tier=TIERS["udp-like"], drop_prob=0.1) >= base
    seq.clear()


# --------------------------------------------------------------------------
# alltoall leading-dim clamp (carried caveat, now closed)
# --------------------------------------------------------------------------

def test_alltoall_prime_leading_dim_prices_executable_segments():
    """Leading dim 12 over 4 ranks = 3 rows/chunk (prime). The flat
    element grid admits pow2 segment counts the ROW grid cannot execute;
    with `lead_dim` the selector's priced k equals the executor's
    clamped k by construction."""
    comm = Communicator(axis="x", size=4, hw=ACCL_CLUSTER)
    sel = Selector()
    lead, row = 12, 16384
    nbytes = lead * row * 4
    flat_pick = sel.choose("alltoall", nbytes, comm)
    row_pick = sel.choose("alltoall", nbytes, comm, lead_dim=lead)
    rows_per_chunk = lead // comm.size
    # the regression this guards: the flat-grid pick is NOT executable
    # on the row grid (it silently clamped below the priced count)
    assert fit_segments(rows_per_chunk, flat_pick.segments,
                        row) != flat_pick.segments
    assert fit_segments(rows_per_chunk, row_pick.segments,
                        row) == row_pick.segments
    assert row_pick.segments > 1  # not vacuous: segmentation still won


def test_alltoall_prime_leading_dim_engine_parity(eng8, rng):
    """End-to-end through the engine on an indivisible leading dim: the
    auto-selected (row-clamped) segment count executes correctly."""
    eng = eng8
    n = 8
    lead, width = 24, 4096  # 3 rows per chunk locally — prime
    data = rng.integers(-30, 30, size=(n * lead, width)).astype(np.float32)
    got = eng.run(lambda x: eng.alltoall(x, "x"),
                  in_specs=P("x"), out_specs=P("x"))(jnp.asarray(data))
    got = np.asarray(got)
    shards = [data[r * lead:(r + 1) * lead] for r in range(n)]
    csize = lead // n
    want = np.concatenate([
        np.concatenate([shards[j][r * csize:(r + 1) * csize]
                        for j in range(n)], axis=0)
        for r in range(n)], axis=0)
    np.testing.assert_array_equal(got, want)
    # the priced choice is executable as-is on the row grid
    comm = eng.comm("x")
    choice = eng.selector.choose(
        "alltoall", lead * width * 4, comm, elem_bytes=4, lead_dim=lead)
    assert fit_segments(lead // n, choice.segments,
                        width) == choice.segments


# --------------------------------------------------------------------------
# The chaos property: bitwise-or-typed-failure, never a hang
# --------------------------------------------------------------------------

_CHAOS_CASES = [
    ("allreduce", "ring"),               # ring
    ("allreduce", "recursive_doubling"), # hypercube
    ("bcast", "binomial_tree"),          # tree
]


@settings(max_examples=24, deadline=None)
@given(data=st.data())
def test_chaos_bitwise_or_typed_failure(eng8, data):
    """For every generated fault schedule, every request either
    materializes bitwise-identical to the fault-free drain (retries
    recovered) or terminates in a typed failure state — zero hangs,
    zero silent corruption."""
    # the CI chaos lane shifts every drawn seed by CHAOS_SEED so each
    # matrix entry exercises a different deterministic schedule family
    seed = data.draw(st.integers(min_value=0, max_value=10_000)) \
        + 20_000 * int(os.environ.get("CHAOS_SEED", "0"))
    drop_prob = data.draw(st.sampled_from([0.0, 0.05, 0.3, 0.9]))
    tier = TIERS[data.draw(st.sampled_from(list(TIERS)))]
    dtype = data.draw(st.sampled_from([np.float32, np.int8]))
    collective, algorithm = data.draw(st.sampled_from(_CHAOS_CASES))
    dead = data.draw(st.sampled_from([(), ((1, 3),), ((6, 0),)]))
    plan = FaultPlan(seed=seed, drop_prob=drop_prob, dead=dead)

    def build(seq):
        kw = {"root": 1} if collective == "bcast" else {}
        reqs = [seq.issue(collective, np.zeros((32,), dtype), "x",
                          algorithm=algorithm, **kw)
                for _ in range(3)]
        # one dependent request so failure cascades are exercised
        reqs.append(seq.issue("allreduce", reqs[0], "x",
                              algorithm="ring"))
        return reqs

    ref_seq = Sequencer(eng8)
    ref_reqs = build(ref_seq)
    ref_out = ref_seq.simulate_drain(_feeds(ref_reqs, seed=seed))

    seq = Sequencer(eng8)
    reqs = build(seq)
    feeds = {r: ref_feed for r, (_rr, ref_feed) in zip(
        [r for r in reqs if not isinstance(r.operand, Request)],
        _feeds(ref_reqs, seed=seed).items())}
    out = seq.simulate_drain(feeds, fault_plan=plan, tier=tier)

    assert seq.outstanding() == []  # the drain returned and is empty
    for r_ref, r in zip(ref_reqs, reqs):
        assert r.finished, "no request may be left in limbo"
        if r.status == Request.DONE:
            for a, b in zip(ref_out[r_ref], out[r]):
                np.testing.assert_array_equal(a, b)
        else:
            assert r.status in (Request.TIMED_OUT, Request.CANCELLED,
                                Request.PEER_FAILED)
            with pytest.raises(Exception):
                r.wait()
