"""Hierarchical cross-fabric collectives (two-level IR programs).

1. Composition parity: every hierarchical composition (allreduce /
   reduce_scatter / allgather / bcast x every inter algorithm) executed
   by the numpy simulator against `simulator.oracle`, on pow2 AND
   non-pow2 intra sizes, {add, max}, {unsegmented, segmented}, and the
   int8 wire codec.
2. Engine parity: the SAME programs executed by the jax engine over a
   real (pod x data) mesh — two-axis ppermutes — match the oracle
   bitwise on integer-valued floats, including the sequential flat
   fallback and the non-zero-root bcast fallback.
3. Pricing invariants: the priced DCN wire bytes of a two-level
   allreduce are EXACTLY 1/ici_size of what the flat per-axis approach
   puts on DCN; the selector picks a hierarchical composition at the
   sizes the issue pins, delegates at degenerate pod sizes, and
   round-trips hierarchical picks through the tuning table.
4. Per-fabric eager caps: a DCN communicator rejects eager at sizes the
   ICI pool still accepts (and an explicit override still wins).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import CollectiveEngine
from repro.core import algorithms as A
from repro.core import hierarchical as H
from repro.core import simulator as sim
from repro.core.selector import Selector
from repro.core.topology import Communicator, make_mesh

COLLECTIVES = ("allreduce", "reduce_scatter", "allgather", "bcast")

# (pod, intra) grids: pow2 x pow2, non-pow2 intra, non-pow2 both
GRIDS = [(2, 2), (2, 3), (4, 3), (3, 2)]


def _pc(P_, M_):
    """(pod=P_ on DCN) x (intra=M_ on ICI) product communicator."""
    return Communicator(axis="pod", size=P_ * M_, is_dcn=True).factor(P_)


def _int_inputs(n, size, seed=0, lo=-8, hi=9):
    """Integer-valued fp32 payloads: add-reductions are exact regardless
    of summation order, so parity checks can be bitwise."""
    rng = np.random.default_rng(seed)
    return [rng.integers(lo, hi, size=size).astype(np.float32)
            for _ in range(n)]


def _sim_run(coll, comm, inter, op="add", segments=None, codec=None,
             per_chunk=12, seed=0):
    sched = H.hierarchical_schedule(coll, comm, intra="ring", inter=inter,
                                    op=op)
    prog = sched.compile(segments=segments, codec=codec)
    n = comm.size
    size = sched.chunks * per_chunk
    inputs = _int_inputs(n, size, seed=seed)
    outs = sim.run_collective(coll, sched, prog, inputs)
    return sched, inputs, outs


# --------------------------------------------------------------------------
# 1. Composition parity in the numpy simulator
# --------------------------------------------------------------------------

@pytest.mark.parametrize("Pp,M", GRIDS, ids=[f"{p}x{m}" for p, m in GRIDS])
@pytest.mark.parametrize("coll", COLLECTIVES)
@pytest.mark.parametrize("segments", [None, 3])
def test_sim_parity(Pp, M, coll, segments):
    """Every composition x every admissible inter algorithm matches the
    oracle exactly (integer-valued fp32)."""
    comm = _pc(Pp, M)
    n = comm.size
    inters = H.inter_candidates(coll, Pp)
    assert inters, (coll, Pp)
    for inter in inters:
        sched, inputs, outs = _sim_run(coll, comm, inter,
                                       segments=segments)
        ref = sim.oracle(coll, inputs)
        if coll == "allreduce":
            for r in range(n):
                np.testing.assert_array_equal(outs[r], ref)
        elif coll == "reduce_scatter":
            csize = inputs[0].size // n
            for r in range(n):
                own = int(sched.owned_chunk(r))
                np.testing.assert_array_equal(
                    outs[r], ref[own * csize:(own + 1) * csize])
        elif coll == "allgather":
            for r in range(n):
                np.testing.assert_array_equal(outs[r], ref)
        else:  # bcast
            for r in range(n):
                np.testing.assert_array_equal(outs[r], inputs[0])


@pytest.mark.parametrize("Pp,M", [(2, 3), (4, 4)])
@pytest.mark.parametrize("op", ["add", "max"])
def test_sim_parity_ops(Pp, M, op):
    """Reducing compositions honour the op at both levels."""
    comm = _pc(Pp, M)
    n = comm.size
    for coll in ("allreduce", "reduce_scatter"):
        for inter in H.inter_candidates(coll, Pp):
            sched, inputs, outs = _sim_run(coll, comm, inter, op=op)
            ref = sim.oracle(coll, inputs, op=op)
            if coll == "allreduce":
                for r in range(n):
                    np.testing.assert_array_equal(outs[r], ref)
            else:
                csize = inputs[0].size // n
                for r in range(n):
                    own = int(sched.owned_chunk(r))
                    np.testing.assert_array_equal(
                        outs[r], ref[own * csize:(own + 1) * csize])


def test_hier_bcast_nonzero_root_raises():
    """The hierarchical bcast lowering is root-0 only (the engine falls
    back to the sequential per-axis path for other roots)."""
    with pytest.raises(ValueError):
        H.hier_bcast(_pc(2, 4), root=1)


def test_degenerate_levels_rejected():
    """Compositions need >= 2 ranks at BOTH levels (the selector
    delegates degenerate products to the live level instead)."""
    with pytest.raises(ValueError):
        H.hierarchical_schedule("allreduce", _pc(1, 4))
    with pytest.raises(ValueError):
        H.hierarchical_schedule("allreduce", _pc(4, 1))


# --------------------------------------------------------------------------
# 2. Pricing invariants + selector behaviour
# --------------------------------------------------------------------------

def test_dcn_wire_bytes_exactly_one_over_ici_size():
    """The headline claim, asserted exactly: a two-level allreduce puts
    1/ici_size of the flat approach's bytes on DCN. Both sides pinned to
    ring so the per-rank scaling (2(P-1)/P) cancels."""
    Pp, M = 4, 4
    comm = _pc(Pp, M)
    msg = float(1 << 20)
    hier = H.hierarchical_schedule("allreduce", comm,
                                   intra="ring", inter="ring").compile()
    got = hier.fabric_wire_bytes(msg, comm)
    # flat: the whole message allreduced over the pod axis rides DCN
    flat = A.GENERATORS[("allreduce", "ring")](comm.outer).compile()
    want = flat.fabric_wire_bytes(msg, comm.outer)
    assert want["dcn"] > 0
    assert got["dcn"] == want["dcn"] / M
    # and the ICI side carries the intra RS + AG (2(M-1)/M per rank)
    assert got["ici"] == pytest.approx(2.0 * (M - 1) / M * msg)


def test_flat_program_prices_identically_on_product():
    """A flat (level=None) program priced over the ProductComm resolves
    every exchange to the bottleneck fabric — bitwise the same cost as
    pricing over the equivalent flat DCN communicator."""
    comm = _pc(4, 4)
    msg = float(1 << 20)
    prog = A.GENERATORS[("allreduce", "ring")](comm.flat).compile()
    assert prog.cost(msg, comm) == prog.cost(msg, comm.flat)
    fb = prog.fabric_wire_bytes(msg, comm)
    assert fb["ici"] == 0.0 and fb["dcn"] > 0


@pytest.mark.parametrize("msg", [1 << 20, 16 << 20],
                         ids=["1MiB", "16MiB"])
def test_selector_picks_hierarchical(msg):
    """Acceptance: on (pod=4 x data=4) TPU_V5E the selector picks a
    hierarchical composition for allreduce at >= 1 MiB."""
    comm = _pc(4, 4)
    c = Selector().choose("allreduce", msg, comm)
    assert c.algorithm.startswith("hierarchical:"), c.algorithm
    assert c.predicted_s > 0
    assert c.program is not None and c.program.level_sizes is not None


@pytest.mark.parametrize("coll", COLLECTIVES)
def test_selector_all_compositions_available(coll):
    """Every composable collective has a hierarchical candidate that can
    win at bandwidth-bound sizes on pod=4 x data=4."""
    c = Selector().choose(coll, 1 << 20, _pc(4, 4))
    assert c.algorithm.startswith("hierarchical:"), (coll, c.algorithm)


def test_selector_delegates_degenerate_pod():
    """pod_size == 1: nothing crosses DCN, so the choice must be a flat
    (non-hierarchical) algorithm — same as choosing over the inner comm."""
    comm = _pc(1, 8)
    c = Selector().choose("allreduce", 1 << 20, comm)
    assert not c.algorithm.startswith("hierarchical:")
    inner = Selector().choose("allreduce", 1 << 20, comm.inner)
    assert (c.algorithm, c.segments) == (inner.algorithm, inner.segments)
    assert c.predicted_s == inner.predicted_s


@pytest.mark.parametrize("nbytes", [1 << 16, 1 << 20, 4 << 20, 16 << 20])
@pytest.mark.parametrize("pod", [2, 4])
def test_selector_hier_beats_flat_at_bandwidth_sizes(pod, nbytes):
    """On a 16-rank (pod x intra) product, from 64 KiB through 16 MiB,
    the hierarchical pick is strictly cheaper than the best flat
    candidate (the reason it wins) and moves fewer bytes over DCN."""
    comm = _pc(pod, 16 // pod)
    sel = Selector()
    c = sel.choose("allreduce", nbytes, comm)
    assert c.algorithm.startswith("hierarchical:"), c
    # price the best flat candidate by pinning the hierarchical family out
    flat_best = min(
        sel.price_program(
            A.GENERATORS[("allreduce", a)](comm.flat).compile(),
            "rendezvous", float(nbytes), comm)
        for a in ("ring", "bidi_ring", "recursive_doubling")
    )
    assert c.predicted_s < flat_best
    # ... and the selector's own flat pick over the all-DCN view, with
    # its segment ladder
    flat = sel.choose("allreduce", nbytes, comm.flat)
    assert c.predicted_s < flat.predicted_s
    dcn_hier = c.program.fabric_wire_bytes(nbytes, comm)["dcn"]
    dcn_flat = flat.program.fabric_wire_bytes(nbytes, comm.flat)["dcn"]
    assert dcn_hier < dcn_flat


def test_table_round_trip_with_hierarchical_names():
    """table_rows -> apply_table reproduces hierarchical picks exactly."""
    comm = _pc(4, 4)
    sizes = (1 << 14, 1 << 20, 16 << 20)
    rows = Selector().table_rows("allreduce", comm, sizes=sizes)
    assert any(r["algorithm"].startswith("hierarchical:") for r in rows)
    fresh = Selector()
    fresh.apply_table(rows)
    for r in rows:
        c = fresh.choose("allreduce", r["msg_bytes"], comm)
        assert (c.algorithm, c.segments) == (r["algorithm"], r["segments"])


def test_dcn_rejects_eager_above_its_own_cap():
    """Per-fabric Rx pools: 48 KiB eager fits the ICI pool (64 KiB cap)
    but NOT the DCN pool (32 KiB cap); an explicit override beats both."""
    ici = Communicator(axis="data", size=4, is_dcn=False)
    dcn = Communicator(axis="pod", size=4, is_dcn=True)
    sel = Selector()
    msg = 48 * 1024
    assert sel._protocol_overhead("eager", msg, ici) is not None
    assert sel._protocol_overhead("eager", msg, dcn) is None
    pinned = Selector(eager_max_bytes=4096)
    assert pinned._protocol_overhead("eager", msg, ici) is None
    assert pinned._protocol_overhead("eager", 2048, dcn) is not None


# --------------------------------------------------------------------------
# 3. Engine parity: two-axis execution on a (pod x data) host mesh
# --------------------------------------------------------------------------

_ENVS = {}


def _env(Pp, M):
    if (Pp, M) not in _ENVS:
        mesh = make_mesh((Pp, M), ("pod", "data"))
        _ENVS[(Pp, M)] = (CollectiveEngine(mesh, backend="microcode"),
                          mesh)
    return _ENVS[(Pp, M)]


def _run2(Pp, M, fn):
    """Run `fn(eng, rank)` under shard_map; rows of the result are the
    per-rank outputs in inner-major flat-rank order."""
    eng, mesh = _env(Pp, M)

    def body():
        r = lax.axis_index("data") * Pp + lax.axis_index("pod")
        return fn(eng, r)[None]

    g = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(),
                              out_specs=P(("data", "pod")),
                              check_vma=False))
    return np.asarray(g())


def _rank_x(r, L):
    """Deterministic integer-valued fp32 payload for flat rank r."""
    base = jnp.arange(L, dtype=jnp.float32)
    return (base % 13.0) * (r + 1.0) - 3.0 * r


def _np_inputs(n, L):
    base = np.arange(L, dtype=np.float32)
    return [(base % 13.0) * (r + 1.0) - 3.0 * r for r in range(n)]


@pytest.mark.parametrize("Pp,M", [(2, 4), (2, 3)],
                         ids=["2x4", "2x3"])
@pytest.mark.parametrize("op", ["add", "max"])
def test_engine_allreduce_hier(Pp, M, op):
    n = Pp * M
    L = 96
    out = _run2(Pp, M, lambda eng, r: eng.allreduce(
        _rank_x(r, L), ("pod", "data"), op=op,
        algorithm="hierarchical:ring+ring"))
    ref = sim.oracle("allreduce", _np_inputs(n, L), op=op)
    for r in range(n):
        np.testing.assert_array_equal(out[r], ref)


def test_engine_reduce_scatter_hier():
    Pp, M = 2, 4
    n = Pp * M
    L = 96
    out = _run2(Pp, M, lambda eng, r: eng.reduce_scatter(
        _rank_x(r, L), ("pod", "data"),
        algorithm="hierarchical:ring+ring"))
    ref = sim.oracle("reduce_scatter", _np_inputs(n, L))
    cs = L // n
    for r in range(n):
        np.testing.assert_array_equal(out[r],
                                      ref[r * cs:(r + 1) * cs])


def test_engine_allgather_hier():
    Pp, M = 2, 4
    n = Pp * M
    L = 24
    out = _run2(Pp, M, lambda eng, r: eng.allgather(
        _rank_x(r, L), ("pod", "data"),
        algorithm="hierarchical:ring+ring"))
    ref = sim.oracle("allgather", _np_inputs(n, L))
    for r in range(n):
        np.testing.assert_array_equal(out[r], ref)


def test_engine_bcast_hier():
    Pp, M = 2, 4
    n = Pp * M
    L = 48
    out = _run2(Pp, M, lambda eng, r: eng.bcast(
        _rank_x(r, L), ("pod", "data"),
        algorithm="hierarchical:ring+binomial_tree"))
    ref = np.asarray(_np_inputs(n, L)[0])
    for r in range(n):
        np.testing.assert_array_equal(out[r], ref)


def test_engine_bcast_nonzero_root_falls_back():
    """root != 0 takes the sequential per-axis fallback and still
    broadcasts the right rank's buffer."""
    Pp, M = 2, 4
    n = Pp * M
    L = 48
    root = 3
    out = _run2(Pp, M, lambda eng, r: eng.bcast(
        _rank_x(r, L), ("pod", "data"), root=root))
    ref = np.asarray(_np_inputs(n, L)[root])
    for r in range(n):
        np.testing.assert_array_equal(out[r], ref)


def test_engine_flat_algorithm_sequential_fallback():
    """An explicit flat algorithm over a product axis executes the
    sequential per-axis composition — still exact."""
    Pp, M = 2, 4
    n = Pp * M
    L = 96
    out = _run2(Pp, M, lambda eng, r: eng.allreduce(
        _rank_x(r, L), ("pod", "data"), algorithm="ring"))
    ref = sim.oracle("allreduce", _np_inputs(n, L))
    for r in range(n):
        np.testing.assert_array_equal(out[r], ref)


def test_engine_codec_hier():
    """int8 wires through the two-axis path: segmented == unsegmented
    bitwise, within quantization tolerance of the oracle."""
    Pp, M = 2, 4
    n = Pp * M
    L = 4096
    rng = np.random.default_rng(7)
    X = (rng.normal(size=(n, L)) * 30).astype(np.float32)

    def call(k):
        def fn(eng, r):
            x = jnp.asarray(X)[r]
            return eng.allreduce(x, ("pod", "data"),
                                 algorithm="hierarchical:ring+ring",
                                 compression="int8", segments=k)
        return fn

    out = _run2(Pp, M, call(4))
    base = _run2(Pp, M, call(1))
    np.testing.assert_array_equal(out, base)
    ref = X.sum(0)
    rel = np.abs(out[0] - ref).max() / np.abs(ref).max()
    assert rel < 0.05, rel


def test_allreduce_multi_two_axes_folds_to_product():
    """allreduce_multi over two axes issues ONE product-communicator
    call (the selector resolves it; the result is exact)."""
    Pp, M = 2, 4
    n = Pp * M
    L = 96
    eng, _ = _env(Pp, M)
    eng.trace_log.clear()
    out = _run2(Pp, M, lambda e, r: e.allreduce_multi(
        _rank_x(r, L), ("data", "pod")))
    ref = sim.oracle("allreduce", _np_inputs(n, L))
    for r in range(n):
        np.testing.assert_array_equal(out[r], ref)
    # one trace entry, tuple axis, resolved (not per-axis ring x2)
    entries = [t for t in eng.trace_log if t[0] == "allreduce"]
    assert len(entries) == 1
    assert entries[0][2] == ("pod", "data")


def test_sequencer_issue_multi_two_axes():
    """The offload queue folds a two-axis gradient sync into one
    product-communicator request; wait() returns the exact sum."""
    Pp, M = 2, 4
    n = Pp * M
    L = 96
    eng, mesh = _env(Pp, M)

    def body():
        r = lax.axis_index("data") * Pp + lax.axis_index("pod")
        req = eng.queue.issue_multi(_rank_x(r, L), ("data", "pod"))
        return req.wait()[None]

    g = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(),
                              out_specs=P(("data", "pod")),
                              check_vma=False))
    out = np.asarray(g())
    ref = sim.oracle("allreduce", _np_inputs(n, L))
    for r in range(n):
        np.testing.assert_array_equal(out[r], ref)
