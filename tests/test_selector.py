"""Algorithm/protocol selector behaviour (paper Table 1 / Fig 12)."""
import pytest

from repro.core import Communicator, Selector


def test_small_message_prefers_low_latency():
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    c = sel.choose("allreduce", 1024, comm)
    assert c.algorithm in ("recursive_doubling",), c
    # latency-optimal: log(n) steps
    assert c.schedule.n_steps() == 3


def test_large_message_prefers_bandwidth_optimal():
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    c = sel.choose("allreduce", 64 << 20, comm)
    assert c.algorithm in ("ring", "bidi_ring", "halving_doubling")
    assert c.schedule.bytes_on_wire(1.0) <= 2.0  # <= 2(n-1)/n + eps


def test_eager_only_below_rx_pool():
    sel = Selector(eager_max_bytes=4096)
    comm = Communicator(axis="x", size=8)
    small = sel.choose("bcast", 1024, comm)
    large = sel.choose("bcast", 1 << 20, comm)
    assert large.protocol == "rendezvous"
    assert small.predicted_s <= large.predicted_s


def test_runtime_tuning_override():
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    auto = sel.choose("allreduce", 1 << 20, comm)
    sel.set_tuning("allreduce", "recursive_doubling")
    tuned = sel.choose("allreduce", 1 << 20, comm)
    assert tuned.algorithm == "recursive_doubling"
    assert auto.algorithm != "recursive_doubling"


def test_reduce_switches_algorithm_with_size():
    """Fig 12: all-to-one for small messages, tree for large."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    small = sel.choose("reduce", 8 << 10, comm)
    large = sel.choose("reduce", 8 << 20, comm)
    assert small.predicted_s < large.predicted_s
    assert large.algorithm == "binomial_tree"


def test_nonpow2_excludes_hypercube():
    sel = Selector()
    comm = Communicator(axis="x", size=6)
    for size in (1024, 1 << 20):
        c = sel.choose("allreduce", size, comm)
        assert c.algorithm in ("ring", "bidi_ring")


# -- tuning-table semantics ---------------------------------------------------

def test_tuning_last_set_rule_wins():
    """Overlapping tuning rules: the most recently set one applies."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    sel.set_tuning("allreduce", "ring")
    sel.set_tuning("allreduce", "recursive_doubling")
    assert sel.choose("allreduce", 1 << 20, comm).algorithm == \
        "recursive_doubling"
    # a later, narrower rule shadows it inside its byte range only
    sel.set_tuning("allreduce", "halving_doubling", lo_bytes=1 << 22)
    assert sel.choose("allreduce", 1 << 20, comm).algorithm == \
        "recursive_doubling"
    assert sel.choose("allreduce", 1 << 23, comm).algorithm == \
        "halving_doubling"


def test_tuning_nranks_filter():
    """nranks-scoped rules apply only to matching communicator sizes."""
    sel = Selector()
    sel.set_tuning("allreduce", "recursive_doubling", nranks=4)
    c8 = sel.choose("allreduce", 64 << 20, Communicator(axis="x", size=8))
    c4 = sel.choose("allreduce", 64 << 20, Communicator(axis="x", size=4))
    assert c4.algorithm == "recursive_doubling"
    assert c8.algorithm != "recursive_doubling"


def test_tuning_pins_segment_count():
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    auto = sel.choose("allreduce", 64 << 20, comm)
    assert auto.segments > 1
    sel.set_tuning("allreduce", auto.algorithm, segments=1)
    pinned = sel.choose("allreduce", 64 << 20, comm)
    assert pinned.algorithm == auto.algorithm
    assert pinned.segments == 1
    assert pinned.predicted_s > auto.predicted_s  # pipelining was winning


def test_eager_cutoff_exact_boundary():
    """eager admissible up to eager_max_bytes inclusive, not beyond."""
    sel = Selector(eager_max_bytes=4096)
    comm = Communicator(axis="x", size=8)
    assert sel._protocol_overhead("eager", 4096, comm) is not None
    assert sel._protocol_overhead("eager", 4097, comm) is None
    assert sel._protocol_overhead("rendezvous", 1 << 30, comm) == \
        comm.hw.rendezvous_rtt


def test_pow2_only_filtering_on_nonpow2_comm():
    """Candidate enumeration drops pow2-only generators on n=6."""
    sel = Selector()
    algos6 = {a for a, _ in sel.candidates("allreduce",
                                           Communicator(axis="x", size=6))}
    algos8 = {a for a, _ in sel.candidates("allreduce",
                                           Communicator(axis="x", size=8))}
    assert algos6 == {"ring", "bidi_ring"}
    assert algos8 == {"ring", "bidi_ring", "recursive_doubling",
                      "halving_doubling"}


# -- memoization --------------------------------------------------------------

def test_choose_is_memoized_zero_generator_calls():
    """Second identical choose() runs no generators and returns the same
    Choice object."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    first = sel.choose("allreduce", 1 << 20, comm)
    gens_after_first = sel.stats["gen_calls"]
    assert gens_after_first > 0
    second = sel.choose("allreduce", 1 << 20, comm)
    assert second is first
    assert sel.stats["gen_calls"] == gens_after_first  # zero new invocations
    assert sel.stats["cache_hits"] == 1
    # a different message size is a different cache entry
    sel.choose("allreduce", 1 << 21, comm)
    assert sel.stats["gen_calls"] > gens_after_first


def test_choose_cache_keys_on_elem_bytes():
    """Codec pricing depends on the element width (wire bytes per elem /
    elem_bytes): a choose() at a different elem_bytes must not be served
    a stale memoized Choice priced for another width."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    c4 = sel.choose("allreduce", 4 << 20, comm, codec="int8", elem_bytes=4)
    c2 = sel.choose("allreduce", 4 << 20, comm, codec="int8", elem_bytes=2)
    assert sel.stats["cache_hits"] == 0  # different width, different entry
    assert c2.predicted_s != c4.predicted_s  # 2-byte wires compress 2x less
    again = sel.choose("allreduce", 4 << 20, comm, codec="int8",
                       elem_bytes=4)
    assert again is c4  # same width still hits the cache
    assert sel.stats["cache_hits"] == 1


def test_set_tuning_invalidates_choose_cache():
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    auto = sel.choose("allreduce", 1 << 20, comm)
    sel.set_tuning("allreduce", "recursive_doubling")
    tuned = sel.choose("allreduce", 1 << 20, comm)
    assert tuned.algorithm == "recursive_doubling"
    assert auto.algorithm != tuned.algorithm


# -- per-fabric segmentation floors (ICI vs DCN) ------------------------------

def test_dcn_axis_prices_its_own_segment_floor():
    """The 10 us DCN alpha + its own min_segment_bytes shift the segment
    optimum: at equal message size the pod axis admits fewer segments and
    chooses a smaller count than the ICI axis."""
    sel = Selector()
    ici = Communicator(axis="data", size=8, is_dcn=False)
    dcn = Communicator(axis="pod", size=8, is_dcn=True)
    assert dcn.min_segment_bytes > ici.min_segment_bytes
    assert dcn.hop_latency > ici.hop_latency

    from repro.core import algorithms as A
    sched = A.ring_allreduce(ici)
    msg = 4 << 20  # per-step chunk = 512 KiB: many ICI segments, few DCN
    adm_ici = sel.admissible_segments(sched, msg, ici)
    adm_dcn = sel.admissible_segments(sched, msg, dcn)
    assert max(adm_ici) > max(adm_dcn)

    c_ici = sel.choose("allreduce", msg, ici)
    c_dcn = sel.choose("allreduce", msg, dcn)
    assert c_ici.segments > c_dcn.segments


def test_compressed_pricing_admits_fewer_segments():
    """Codec wires shrink per-segment bytes, so the same message admits
    fewer segment counts under compression (the Rx floor is on wire
    bytes)."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    from repro.core import algorithms as A
    sched = A.ring_allreduce(comm)
    msg = 1 << 20
    plain = sel.admissible_segments(sched, msg, comm)
    packed = sel.admissible_segments(sched, msg, comm, codec="int8")
    assert max(packed) < max(plain)
    ch = sel.choose("allreduce", msg, comm, codec="int8")
    assert ch.codec == "int8" and ch.compressed


# -- lossless tuning-table round-trip -----------------------------------------

def test_table_reports_segments_and_codec():
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    rows = sel.table_rows("allreduce", comm)
    assert {r["msg_bytes"] for r in rows} == set(
        Selector.DEFAULT_TABLE_SIZES)
    big = next(r for r in rows if r["msg_bytes"] == 1 << 27)
    assert big["segments"] > 1           # large messages pipeline
    assert big["compressed"] is False
    assert all({"algorithm", "protocol", "segments", "codec",
                "nranks"} <= set(r) for r in rows)


@pytest.mark.parametrize("codec", [None, "int8"])
def test_table_round_trip_is_lossless(codec):
    """table_rows -> apply_table on a fresh selector reproduces every
    bucket's (algorithm, segments) exactly — nothing is dropped on the
    way through benchmark output and back."""
    src = Selector()
    comm = Communicator(axis="x", size=8)
    rows = src.table_rows("allreduce", comm, codec=codec)

    dst = Selector()
    dst.apply_table(rows)
    for r in rows:
        c = dst.choose("allreduce", r["msg_bytes"], comm, codec=codec)
        assert c.algorithm == r["algorithm"], r
        assert c.segments == r["segments"], r


def test_compressed_table_does_not_leak_into_uncompressed_choose():
    """Tuning entries carry the codec they were measured under: a table
    priced on int8 wires must not override uncompressed selection."""
    comm = Communicator(axis="x", size=8)
    baseline = Selector().choose("allreduce", 1 << 24, comm)
    sel = Selector()
    sel.apply_table(sel.table_rows("allreduce", comm, codec="int8"))
    plain = sel.choose("allreduce", 1 << 24, comm)
    assert (plain.algorithm, plain.segments) == \
        (baseline.algorithm, baseline.segments)


# -- custom-collective candidates ---------------------------------------------

def _pow2_only_gen(comm):
    if not comm.is_pow2:
        raise ValueError("needs power-of-two ranks")
    from repro.core import algorithms as A
    return A.ring_allreduce(comm)


def test_inapplicable_custom_generator_is_skipped_not_fatal():
    """A registered generator that raises for this communicator (e.g.
    pow2-only) must be skipped by the auto sweep, like the built-ins'
    pow2 filter — not crash the whole choose()."""
    from repro.core import plugins
    from repro.core import algorithms as A
    plugins.register_collective("myred", _pow2_only_gen, algorithm="pow2")
    plugins.register_collective(
        "myred", lambda comm: A.ring_allreduce(comm), algorithm="ring")
    try:
        sel = Selector()
        c = sel.choose("myred", 1 << 20, Communicator(axis="x", size=6))
        assert c.algorithm == "ring"
        c8 = sel.choose("myred", 1 << 10, Communicator(axis="x", size=8))
        assert c8.algorithm in ("pow2", "ring")
    finally:
        plugins.unregister_collective("myred")


def test_registry_changes_invalidate_choose_cache():
    """Registering a cheaper algorithm after a choose() must be visible
    on the next identical choose (no stale registry picks)."""
    from repro.core import plugins
    from repro.core import algorithms as A
    comm = Communicator(axis="x", size=8)
    sel = Selector()
    plugins.register_collective(
        "myred2", lambda comm: A.ring_reduce(comm), algorithm="slow_ring")
    try:
        first = sel.choose("myred2", 1 << 20, comm)
        assert first.algorithm == "slow_ring"
        plugins.register_collective(
            "myred2", lambda comm: A.ring_allreduce(comm), algorithm="ring")
        second = sel.choose("myred2", 1 << 20, comm)
        assert second.algorithm == "ring"  # cheaper newcomer wins
        plugins.unregister_collective("myred2", "ring")
        third = sel.choose("myred2", 1 << 20, comm)
        assert third.algorithm == "slow_ring"
    finally:
        plugins.unregister_collective("myred2")


def test_choice_segments_always_executable_on_indivisible_payload():
    """ROADMAP "prices requested k" item, closed: every candidate
    segment count is clamped through `fit_segments` on the padded chunk
    grid BEFORE pricing, so `Choice.segments` is exactly the count the
    executor's trace-time clamp will admit — never a priced fiction the
    data plane then shrinks."""
    from repro.core.program import fit_segments
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    # 3^8 fp32 elements per chunk: no power-of-two count divides it, so
    # the old selector would price (and "choose") k=2 for the streamed
    # ring while the executor silently ran k=1
    msg = 8 * 6561 * 4
    c = sel.choose("allreduce", msg, comm)
    csize = (msg // 4) // 8
    assert csize % c.segments == 0           # executable as priced
    assert c.segments == fit_segments(csize, c.segments)


def test_tuned_segment_pin_clamped_to_executable_count():
    """A tuning-table segment pin on an indivisible payload prices the
    count the executor will actually run (the largest admissible
    divisor), keeping cost and execution in agreement for pinned
    deployments too."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    msg = 8 * 6561 * 4
    sel.set_tuning("allreduce", "ring", segments=4)
    c = sel.choose("allreduce", msg, comm)
    assert c.algorithm == "ring"
    assert c.segments == 3                   # fit_segments(6561, 4) == 3


def test_divisible_payload_choices_unchanged_by_clamp():
    """Power-of-two payloads (every benchmark sweep point) admit the
    full candidate ladder: the clamp is the identity there."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    c = sel.choose("allreduce", 1 << 20, comm)
    csize = ((1 << 20) // 4) // 8
    assert csize % c.segments == 0
    assert c.segments > 1  # large streamed message still segments


def test_gather_shard_clamp_uses_shard_grid():
    """Regression: allgather/gather price the per-rank SHARD but execute
    on the nranks*shard buffer whose chunk IS one shard — the clamp must
    fit candidates against the shard, not shard/chunks (which would
    wrongly collapse the ladder for non-power-of-two shards)."""
    from repro.core import algorithms as A
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    sched = A.ring_allgather(comm)
    # 24-element fp32 shard: the shard grid admits 2, 4, and 8; the
    # wrong shard/chunks grid (3 elements) would collapse to (1, 3)
    assert sel.fit_candidate_segments(sched, 24 * 4, (1, 2, 4, 8)) == \
        (1, 2, 4, 8)


# --------------------------------------------------------------------------
# The measured v5e: a stream's waves run one after another
# --------------------------------------------------------------------------

FIG10 = ("allreduce", "reduce_scatter", "allgather", "bcast", "reduce",
         "gather", "alltoall")


def _engine4(hw):
    from repro.core import CollectiveEngine
    from repro.core.topology import make_mesh
    return CollectiveEngine(make_mesh((4,), ("x",)), hw=hw)


def _pick(eng, collective, size):
    c = eng.selector.choose(collective, size, eng.comm("x"))
    return c.algorithm, c.protocol, c.segments


@pytest.mark.parametrize("size", [4096, 131072])
@pytest.mark.parametrize("collective", FIG10)
def test_measured_v5e_keeps_small_picks(collective, size):
    """No small pick of the collective grid streams, and a stream's
    price can only rise under serialized waves: the measured spec picks
    exactly what the modelled one does at 4 KiB and 128 KiB."""
    from repro.core.hw_spec import TPU_V5E, TPU_V5E_MEASURED
    assert _pick(_engine4(TPU_V5E_MEASURED), collective, size) == \
        _pick(_engine4(TPU_V5E), collective, size)


@pytest.mark.parametrize("collective", ["allreduce", "reduce_scatter"])
def test_measured_v5e_picks_no_stream_at_4mib(collective):
    """At 4 MiB the modelled spec streams these in 8 segments; on the
    measured chip each wave of a reducing stream costs its own alpha, so
    one unsegmented wave per step wins."""
    from repro.core.hw_spec import TPU_V5E, TPU_V5E_MEASURED
    from repro.core.program import Stream, StreamChain
    assert _pick(_engine4(TPU_V5E), collective, 4 << 20)[2] == 8
    eng = _engine4(TPU_V5E_MEASURED)
    c = eng.selector.choose(collective, 4 << 20, eng.comm("x"))
    assert c.segments == 1, c
    assert not any(isinstance(op, (Stream, StreamChain))
                   for op in c.program.ops)


@pytest.mark.parametrize("collective", ["allgather", "alltoall", "bcast",
                                        "gather"])
def test_measured_v5e_keeps_copy_picks_at_4mib(collective):
    """Copy-only programs keep the drain credit on the measured chip
    (their streamed forms beat their unsegmented ones there): the 4 MiB
    picks, the streamed allgather and all-to-all among them, stay."""
    from repro.core.hw_spec import TPU_V5E, TPU_V5E_MEASURED
    assert _pick(_engine4(TPU_V5E_MEASURED), collective, 4 << 20) == \
        _pick(_engine4(TPU_V5E), collective, 4 << 20)


def test_serial_wave_counters_count():
    """Each auto pick under serialized waves counts once; a pick that
    comes out segmented counts in the streamed counter; an explicit
    algorithm is no pick and counts in neither."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.hw_spec import TPU_V5E, TPU_V5E_MEASURED

    def traced(eng, **kw):
        f = jax.shard_map(lambda v: eng.allreduce(v[0], "x", **kw)[None],
                          mesh=eng.mesh, in_specs=P("x"), out_specs=P("x"),
                          check_vma=False)
        jax.eval_shape(f, jax.ShapeDtypeStruct((4, 1 << 20), jnp.float32))
        return (eng.metrics.get("selector.serial_wave_choices"),
                eng.metrics.get("selector.streamed_choices"))

    measured = _engine4(TPU_V5E_MEASURED)
    assert traced(measured) == (1, 0)
    assert traced(measured) == (2, 0)
    assert traced(measured, algorithm="bidi_ring", segments=8) == (2, 0)
    assert traced(_engine4(TPU_V5E)) == (0, 1)
