"""Program-level pricing: `Program.cost` is the one cost model.

1. Split-model pricing against the goldens (tests/golden_pricing.py):
   k=1 programs and k>1 programs that fuse into ONE cross-step region
   still reproduce the retired schedule-walk `predict_time` EXACTLY —
   the credit is earned there. SEG_LOOP-only programs reproduce the
   serialized `predict_time_segloop` EXACTLY and intentionally price
   ABOVE the old walk (the old model over-credited them); multi-region
   and mixed programs sit strictly between the two goldens, with the
   ring-allreduce divergence pinned to its closed form.
2. The passes: STREAM/STREAM_CHAIN fusion now EARNS the cross-step
   credit (fused prices below unfused); stacked receives stay neutral.
3. Per-fabric floors: segment counts that would cut an exchange's wire
   payload below the Rx floor are clamped in the walk (the schedule walk
   priced them as if the Rx buffers were infinite).
4. The selector's hot path prices the compiled program (Choice.program),
   `Schedule` has no pricing method left to walk, and the simulator and
   engine agree on the cost of the program they both execute.
"""
import inspect
import math

import numpy as np
import pytest

import golden_pricing as GP
from repro.core import Communicator, Selector
from repro.core import algorithms as A
from repro.core import simulator as sim
from repro.core.schedule import Schedule
from repro.core.hw_spec import ACCL_CLUSTER
from repro.core.program import Stream, StreamChain, compile_schedule

COMM8 = Communicator(axis="x", size=8)

ALL_ALGOS = sorted({(c, a) for (c, a) in A.GENERATORS})


def _gen(coll, algo, comm):
    gen = A.GENERATORS[(coll, algo)]
    kw = {"root": 1} if "root" in inspect.signature(gen).parameters else {}
    return gen(comm, **kw)


def _wire_scale(codec, elem_bytes=4):
    if codec is None:
        return 1.0
    from repro.core import plugins
    return plugins.get_codec(codec).wire_bytes_per_elem / elem_bytes


def _regions(prog):
    return [op for op in prog.ops if isinstance(op, (Stream, StreamChain))]


def _loose_exchanges(prog):
    """Exchanges priced OUTSIDE any cross-step region (serialized)."""
    return [t for t in prog.exchange_terms() if t[3] is None]


# -- 1. split-model pricing against the goldens -------------------------------

@pytest.mark.parametrize("coll,algo", ALL_ALGOS,
                         ids=[f"{c}-{a}" for c, a in ALL_ALGOS])
@pytest.mark.parametrize("codec", [None, "int8"])
def test_cost_against_goldens_scoped(coll, algo, codec):
    """Every algorithm x segment count x codec, scoped by what the
    compiled program can actually execute. Message sizes keep every
    per-segment wire payload above the ICI floor so the floor clamp
    never fires — the regime the old model priced."""
    sched = _gen(coll, algo, COMM8)
    for msg in (4 << 20, 64 << 20):
        for k in (1, 2, 4, 8):
            old = GP.predict_time(sched, msg, COMM8.hop_latency,
                                  COMM8.link_bw, segments=k,
                                  wire_scale=_wire_scale(codec))
            serial = GP.predict_time_segloop(
                sched, msg, COMM8.hop_latency, COMM8.link_bw, segments=k,
                wire_scale=_wire_scale(codec))
            prog = compile_schedule(sched, segments=k, codec=codec)
            got = prog.cost(msg, COMM8)
            regions = _regions(prog)
            loose = _loose_exchanges(prog)
            if k == 1 or (len(regions) == 1 and not loose):
                # the whole program is one cross-step pipeline: the old
                # credit is earned in full, parity survives exactly
                assert math.isclose(got, old, rel_tol=1e-12), (msg, k)
            elif not regions:
                # SEG_LOOP-only: serialized steps, honest price ABOVE
                # the old walk's cross-step credit
                assert math.isclose(got, serial, rel_tol=1e-12), (msg, k)
                assert got > old, (msg, k)
            else:
                # multi-region (ring allreduce: RS + AG streams) or
                # mixed: part of the credit is earned, never all of it
                assert old < got < serial, (msg, k)


def test_ring_allreduce_divergence_is_the_extra_drain():
    """The intentional ring-allreduce divergence, pinned exactly: its RS
    and AG phases stream as TWO regions with a barrier between them, so
    the program pays one extra (k-1)*t_seg drain over the old
    single-pipeline walk."""
    sched = A.ring_allreduce(COMM8)
    msg = 8 << 20
    for k in (2, 8):
        old = GP.predict_time(sched, msg, COMM8.hop_latency,
                              COMM8.link_bw, segments=k)
        got = compile_schedule(sched, segments=k).cost(msg, COMM8)
        t_seg = COMM8.hop_latency + (msg / 8) / (k * COMM8.link_bw)
        assert math.isclose(got, old + (k - 1) * t_seg, rel_tol=1e-12)


@pytest.mark.parametrize("k", [3, 4, 8])
def test_recursive_halving_earns_full_parity_via_chain(k):
    """The SEL_RANGE overlap proof admits recursive halving at k >= 3:
    the whole schedule fuses into ONE STREAM_CHAIN and wins back exactly
    the price the old walk always granted it. At k = 2 the proof fails
    (the head segment reaches into the missing tail write), the program
    stays SEG_LOOP-only, and the price is the honest serialized one."""
    sched = A.recursive_halving_reduce_scatter(COMM8)
    msg = 16 << 20
    prog = compile_schedule(sched, segments=k)
    assert [type(op) for op in prog.ops] == [StreamChain]
    old = GP.predict_time(sched, msg, COMM8.hop_latency, COMM8.link_bw,
                          segments=k)
    assert math.isclose(prog.cost(msg, COMM8), old, rel_tol=1e-12)

    k2 = compile_schedule(sched, segments=2)
    assert not _regions(k2)
    serial = GP.predict_time_segloop(sched, msg, COMM8.hop_latency,
                                     COMM8.link_bw, segments=2)
    assert math.isclose(k2.cost(msg, COMM8), serial, rel_tol=1e-12)


def test_cost_parity_nonpow2_and_other_fabric():
    """Single-region parity holds off the 8-rank/TPU happy path too."""
    accl = Communicator(axis="x", size=6, hw=ACCL_CLUSTER)
    for coll, algo in (("reduce_scatter", "ring"), ("reduce", "ring")):
        sched = _gen(coll, algo, accl)
        for k in (1, 4):
            want = GP.predict_time(sched, 16 << 20, accl.hop_latency,
                                   accl.link_bw, segments=k)
            got = compile_schedule(sched, segments=k).cost(16 << 20, accl)
            assert math.isclose(want, got, rel_tol=1e-12)


# -- 2. the passes and the price ----------------------------------------------

@pytest.mark.parametrize("coll,algo",
                         [("allreduce", "ring"), ("allreduce", "bidi_ring"),
                          ("reduce", "ring"), ("allgather", "ring"),
                          ("reduce_scatter", "recursive_halving"),
                          ("allreduce", "halving_doubling")])
def test_stream_fusion_earns_the_credit(coll, algo):
    """The split model prices the fused and unfused forms differently —
    only the program that actually keeps the wire busy across step
    boundaries gets the cross-step credit. The unfused form prices at
    the serialized golden model."""
    sched = _gen(coll, algo, COMM8)
    for k in (4, 8):
        fused = compile_schedule(sched, segments=k)
        plain = compile_schedule(sched, segments=k, stream=False)
        assert _regions(fused) and not _regions(plain)
        assert fused.cost(8 << 20, COMM8) < plain.cost(8 << 20, COMM8)
        serial = GP.predict_time_segloop(
            sched, 8 << 20, COMM8.hop_latency, COMM8.link_bw, segments=k)
        assert math.isclose(plain.cost(8 << 20, COMM8), serial,
                            rel_tol=1e-12)


def test_stacked_recv_is_price_neutral():
    sched = A.linear_alltoall(COMM8)
    stacked = compile_schedule(sched)
    plain = compile_schedule(sched, stacked=False)
    assert stacked.ops != plain.ops
    assert stacked.cost(8 << 20, COMM8) == plain.cost(8 << 20, COMM8)


# -- 3. per-fabric segment floors in the walk ---------------------------------

def test_cost_clamps_sub_floor_segments():
    """A pinned segment count that cuts the wire below the fabric floor
    prices at the clamped count — the Rx buffers cannot hold thinner
    segments, so the walk must not credit them. On DCN (256 KiB floor) a
    1 MiB ring step (128 KiB chunks) admits no segmentation at all."""
    dcn = Communicator(axis="pod", size=8, is_dcn=True)
    sched = A.ring_allreduce(dcn)
    msg = 1 << 20
    k8 = compile_schedule(sched, segments=8).cost(msg, dcn)
    k1 = compile_schedule(sched, segments=1).cost(msg, dcn)
    assert k8 == k1  # clamped all the way back to unsegmented
    # same program on ICI (8 KiB floor): k=8 keeps its fill/drain credit
    ici = Communicator(axis="x", size=8)
    assert compile_schedule(sched, segments=8).cost(msg, ici) < \
        compile_schedule(sched, segments=1).cost(msg, ici)


def test_cost_floor_partial_clamp_monotone():
    """Between the extremes the clamp is partial: the price of an
    over-segmented program sits between the admissible optimum and the
    unsegmented baseline."""
    dcn = Communicator(axis="pod", size=8, is_dcn=True)
    sched = A.ring_allreduce(dcn)
    msg = 16 << 20  # 2 MiB steps: floor admits k <= 8
    c4 = compile_schedule(sched, segments=4).cost(msg, dcn)
    c32 = compile_schedule(sched, segments=32).cost(msg, dcn)
    c8 = compile_schedule(sched, segments=8).cost(msg, dcn)
    c1 = compile_schedule(sched, segments=1).cost(msg, dcn)
    assert c8 == c32  # 32 clamps to the floor count, 8
    assert c4 < c1 and c8 < c1


# -- 4. the selector prices the compiled artifact -----------------------------

def test_schedule_has_no_pricing_walk():
    """The schedule-walk pricer is retired (mirrors the CI grep guard):
    cost lives on the Program alone."""
    assert not hasattr(Schedule, "predict_time")


def test_choice_carries_the_priced_program():
    """choose() attaches the exact compiled program it priced, and the
    price decomposes as program cost + protocol overhead."""
    sel = Selector()
    for coll, msg in (("allreduce", 4 << 20), ("reduce", 8 << 10)):
        c = sel.choose(coll, msg, COMM8)
        assert c.program is not None
        assert c.program.segments == c.segments
        ov = sel._protocol_overhead(c.protocol, msg, COMM8)
        assert math.isclose(c.predicted_s,
                            c.program.cost(msg, COMM8) + ov, rel_tol=1e-12)


def test_priced_program_is_the_executed_program():
    """The engine's memoized compile of the chosen schedule returns THE
    program object the selector priced — one artifact for cost and
    execution, compiled once."""
    sel = Selector()
    c = sel.choose("allreduce", 4 << 20, COMM8)
    executed = c.schedule.compile(codec=c.codec)
    assert executed is c.program


def test_simulator_returns_the_cost_it_executes():
    """simulate_with_cost prices the same compiled program it ran."""
    sched = A.ring_allreduce(COMM8)
    xs = [np.full((16,), float(r), np.float32) for r in range(8)]
    bufs, t = sim.simulate_with_cost(sched, xs, COMM8, segments=4)
    for b in bufs:
        np.testing.assert_allclose(b, np.full((16,), 28.0), atol=1e-5)
    assert t == compile_schedule(sched, segments=4).cost(
        xs[0].nbytes, COMM8)


@pytest.mark.parametrize("gen", [A.ring_allreduce,
                                 A.recursive_halving_reduce_scatter],
                         ids=["ring", "recursive_halving"])
def test_simulator_and_engine_agree_on_cost(gen):
    """The simulator's reported cost is the cost of the engine-side
    artifact: `simulate_with_cost` and the selector's `price_program`
    walk the SAME memoized compile, so model evaluation and execution
    can never quote different numbers for one program."""
    sched = gen(COMM8)
    xs = [np.arange(64, dtype=np.float32) + r for r in range(8)]
    for k in (1, 4):
        _bufs, t = sim.simulate_with_cost(sched, xs, COMM8, segments=k)
        engine_prog = sched.with_segments(k).compile()
        assert t == engine_prog.cost(xs[0].nbytes, COMM8)
        sel = Selector()
        priced = sel.price_program(engine_prog, "rendezvous",
                                   xs[0].nbytes, COMM8)
        assert math.isclose(
            priced, t + COMM8.hw.rendezvous_rtt, rel_tol=1e-12)


def test_streamed_and_segloop_costs_disagree_where_the_model_says():
    """The split model is visible through simulate_with_cost: the same
    schedule executed streamed vs stream=False returns identical buffers
    but different costs — only the streamed program earns the cross-step
    credit. (Identical costs here would mean the old, dishonest model.)"""
    sched = A.ring_reduce_scatter(COMM8)
    # large enough that the per-segment wire payload clears the Rx floor
    xs = [np.arange(1 << 16, dtype=np.float32) + r for r in range(8)]
    fused_bufs, t_fused = sim.simulate_with_cost(sched, xs, COMM8,
                                                 segments=4)
    plain_bufs, t_plain = sim.simulate_with_cost(sched, xs, COMM8,
                                                 segments=4, stream=False)
    for a, b in zip(fused_bufs, plain_bufs):
        np.testing.assert_array_equal(a, b)
    assert t_fused < t_plain
    assert t_plain == compile_schedule(sched, segments=4,
                                       stream=False).cost(
        xs[0].nbytes, COMM8)


def test_compile_rejects_zero_segments():
    with pytest.raises(ValueError):
        compile_schedule(A.ring_reduce_scatter(COMM8), segments=0)


# --------------------------------------------------------------------------
# Serialized stream waves (the measured v5e)
# --------------------------------------------------------------------------

REDUCING_STREAMS = (
    [("allreduce", "bidi_ring", k) for k in (2, 4, 8)]
    + [("allreduce", "ring", k) for k in (2, 8)]
    + [("reduce_scatter", "ring", k) for k in (2, 4, 8)]
    + [("reduce_scatter", "recursive_halving", k) for k in (4, 8)]
    + [("allreduce", "halving_doubling", 8)]
)
COPY_STREAMS = (
    [("allgather", "ring", k) for k in (2, 8)]
    + [("allgather", "recursive_doubling", 8)]
    + [("alltoall", "linear", k) for k in (2, 8)]
)


def _streamed(coll, algo, k):
    prog = _gen(coll, algo, Communicator(axis="x", size=4)).with_segments(
        k).compile()
    waves = sum(op.trip * op.segments if isinstance(op, Stream)
                else len(op.bodies) * op.segments
                for op in prog.ops if isinstance(op, (Stream, StreamChain)))
    assert waves >= 2 * k
    return prog, waves


@pytest.mark.parametrize("coll,algo,k", REDUCING_STREAMS,
                         ids=[f"{c}-{a}-k{k}" for c, a, k
                              in REDUCING_STREAMS])
def test_serial_waves_price_at_least_every_wave(coll, algo, k):
    """Under serialized waves a reducing program's stream costs at least
    its trip x k waves' alphas plus its busiest link's wire bytes over
    `ici_link_bw` (each link direction of a bidi ring carries half), so
    no stream is priced below the wire floor the modelled spec's drain
    credit dips under (4 MiB ring reduce-scatter at k=8: 38 us against a
    63 us floor)."""
    from repro.core.hw_spec import TPU_V5E, TPU_V5E_MEASURED
    msg = 4 << 20
    prog, waves = _streamed(coll, algo, k)
    assert prog.reduces
    measured = Communicator(axis="x", size=4, hw=TPU_V5E_MEASURED)
    busiest = (prog.fabric_wire_bytes(msg, measured)["ici"]
               / prog.overlap_factor)
    floor = waves * TPU_V5E.ici_hop_latency + busiest / TPU_V5E.ici_link_bw
    assert prog.cost(msg, measured) >= floor * (1 - 1e-12)
    modelled = prog.cost(msg, Communicator(axis="x", size=4, hw=TPU_V5E))
    assert modelled < prog.cost(msg, measured)


@pytest.mark.parametrize("coll,algo,k", COPY_STREAMS,
                         ids=[f"{c}-{a}-k{k}" for c, a, k in COPY_STREAMS])
def test_copy_streams_keep_the_credit_on_the_measured_spec(coll, algo, k):
    """A copy-only program prices bitwise as on the modelled spec."""
    from repro.core.hw_spec import TPU_V5E, TPU_V5E_MEASURED
    prog, _waves = _streamed(coll, algo, k)
    assert not prog.reduces
    assert prog.cost(4 << 20, Communicator(axis="x", size=4,
                                           hw=TPU_V5E_MEASURED)) == \
        prog.cost(4 << 20, Communicator(axis="x", size=4, hw=TPU_V5E))


def test_serial_waves_equal_the_unfused_program():
    """With no drain credit a streamed region prices as its unfused
    (stream=False) compile does: the executor's k waves each pay alpha."""
    from repro.core.hw_spec import TPU_V5E_MEASURED
    comm = Communicator(axis="x", size=4, hw=TPU_V5E_MEASURED)
    sched = A.ring_reduce_scatter(comm)
    fused = compile_schedule(sched, segments=8)
    plain = compile_schedule(sched, segments=8, stream=False)
    assert any(isinstance(op, Stream) for op in fused.ops)
    assert fused.cost(4 << 20, comm) == plain.cost(4 << 20, comm)
    lat, wire, links = fused.cost_terms(4 << 20, comm, per_link=True)
    assert math.isclose(lat + wire, fused.cost(4 << 20, comm),
                        rel_tol=1e-12)
    assert links == {("ici", "x"): wire}
