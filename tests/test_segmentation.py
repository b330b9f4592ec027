"""Segmented, double-buffered collective lowerings (ACCL+ §4.4.3).

Parity: every segmented lowering must be numerics-identical to the
unsegmented one (segments cut elementwise combines into disjoint pieces,
so uncompressed paths are bitwise-equal). Model: for large messages the
pipelined alpha-beta prediction strictly dominates the 1-segment baseline
exactly where the compiled program streams.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import CollectiveEngine, Communicator, Selector
from repro.core import algorithms as A
from repro.core.engine import _fit_segments, _gen_schedule
from repro.core.program import Stream, StreamChain


@pytest.fixture(scope="module")
def eng8():
    from repro.core.topology import make_mesh
    mesh = make_mesh((8,), ("x",))
    return CollectiveEngine(mesh, backend="microcode"), mesh


def run(mesh, fn, x, in_spec=P("x"), out_spec=P("x")):
    g = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_spec,
                              out_specs=out_spec, check_vma=False))
    return np.asarray(g(jnp.asarray(x)))


X = np.random.default_rng(7).normal(size=(8, 32, 4)).astype(np.float32)


# -- data-plane parity across segment counts ---------------------------------

@pytest.mark.parametrize("algo", ["ring", "bidi_ring"])
@pytest.mark.parametrize("segments", [2, 3, 4, 8])
def test_allreduce_ring_segment_parity(eng8, algo, segments):
    eng, mesh = eng8
    base = run(mesh, lambda xs: eng.allreduce(
        xs[0], "x", algorithm=algo, segments=1)[None], X)
    seg = run(mesh, lambda xs: eng.allreduce(
        xs[0], "x", algorithm=algo, segments=segments)[None], X)
    np.testing.assert_array_equal(seg, base)
    for r in range(8):
        np.testing.assert_allclose(seg[r], X.sum(0), atol=1e-4)


@pytest.mark.parametrize("algo", ["recursive_doubling", "halving_doubling"])
@pytest.mark.parametrize("segments", [2, 4])
def test_allreduce_interpreted_segment_parity(eng8, algo, segments):
    """Hypercube schedules run through the segmented interpreter path."""
    eng, mesh = eng8
    base = run(mesh, lambda xs: eng.allreduce(
        xs[0], "x", algorithm=algo, segments=1)[None], X)
    seg = run(mesh, lambda xs: eng.allreduce(
        xs[0], "x", algorithm=algo, segments=segments)[None], X)
    np.testing.assert_allclose(seg, base, atol=1e-5)
    for r in range(8):
        np.testing.assert_allclose(seg[r], X.sum(0), atol=1e-4)


@pytest.mark.parametrize("segments", [2, 3, 4])
def test_reduce_scatter_segment_parity(eng8, segments):
    eng, mesh = eng8
    flat = X.reshape(8, -1)
    cs = flat.shape[1] // 8
    base = run(mesh, lambda xs: eng.reduce_scatter(
        xs[0], "x", algorithm="ring", segments=1)[None], X)
    seg = run(mesh, lambda xs: eng.reduce_scatter(
        xs[0], "x", algorithm="ring", segments=segments)[None], X)
    np.testing.assert_array_equal(seg, base)
    for r in range(8):
        np.testing.assert_allclose(seg[r], flat.sum(0)[r * cs:(r + 1) * cs],
                                   atol=1e-4)


@pytest.mark.parametrize("segments", [2, 4, 8])
def test_allgather_segment_parity(eng8, segments):
    eng, mesh = eng8
    base = run(mesh, lambda xs: eng.allgather(
        xs[0], "x", algorithm="ring", segments=1)[None], X)
    seg = run(mesh, lambda xs: eng.allgather(
        xs[0], "x", algorithm="ring", segments=segments)[None], X)
    np.testing.assert_array_equal(seg, base)
    np.testing.assert_allclose(seg[0], X.reshape(-1))


@pytest.mark.parametrize("op", ["max", "min", "mul"])
def test_segmented_nonadd_ops(eng8, op):
    eng, mesh = eng8
    Xp = np.abs(X) + 0.5  # keep mul well-conditioned
    base = run(mesh, lambda xs: eng.allreduce(
        xs[0], "x", op=op, algorithm="ring", segments=1)[None], Xp)
    seg = run(mesh, lambda xs: eng.allreduce(
        xs[0], "x", op=op, algorithm="ring", segments=4)[None], Xp)
    np.testing.assert_array_equal(seg, base)


def test_compressed_auto_allreduce_scale_reuse_parity(eng8):
    """The selector prices compressed-segmented variants (codec-aware
    choose) and the data plane guarantees per-segment scale reuse: the
    executor only admits segment sizes that are whole codec scale blocks,
    so the auto-segmented compressed wire is BITWISE-identical to the
    unsegmented codec — auto == explicit (same algorithm, segments=1)."""
    eng, mesh = eng8
    # 4 MiB: large enough that the codec-aware auto pick segments a
    # STREAMED algorithm under the split model (smaller compressed
    # messages now honestly prefer the unsegmented hypercube)
    big = np.random.default_rng(9).normal(
        size=(8, 1 << 20)).astype(np.float32)
    nbytes = big[0].nbytes
    ch = eng.selector.choose("allreduce", nbytes, eng.comm("x"),
                             codec="int8")
    assert ch.segments > 1  # the codec-aware auto pick segments this size
    assert ch.compressed and ch.codec == "int8"

    def call(algorithm, segments):
        g = jax.jit(jax.shard_map(
            lambda v: eng.allreduce(v, "x", algorithm=algorithm,
                                    compression="int8", segments=segments),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
        return np.asarray(g(jnp.asarray(big)))

    auto = call("auto", None)
    k1 = call(ch.algorithm, 1)
    np.testing.assert_array_equal(auto, k1)


def test_segmented_compressed_allreduce(eng8):
    """Codec paths stay within quantization tolerance when segmented."""
    eng, mesh = eng8
    out = run(mesh, lambda xs: eng.allreduce(
        xs[0] * 40, "x", algorithm="ring", compression="int8",
        segments=4)[None], X)
    ref = X.sum(0) * 40
    rel = np.abs(out[0] - ref).max() / np.abs(ref).max()
    assert rel < 0.02


# -- grad-path parity ---------------------------------------------------------

@pytest.mark.parametrize("algo", ["ring", "bidi_ring"])
def test_allreduce_grad_segment_parity(eng8, algo):
    eng, mesh = eng8

    def make_loss(segments):
        def loss(v):
            y = eng.allreduce(v, "x", algorithm=algo, segments=segments)
            return (y ** 3).sum()
        return loss

    grads = {}
    for segments in (1, 4):
        g = jax.jit(jax.shard_map(
            jax.grad(make_loss(segments)), mesh=mesh, in_specs=P("x"),
            out_specs=P("x"), check_vma=False))
        grads[segments] = np.asarray(g(jnp.asarray(X.reshape(8, -1))))
    np.testing.assert_allclose(grads[4], grads[1], atol=1e-5)


def test_allgather_grad_segment_parity(eng8):
    eng, mesh = eng8

    def make_loss(segments):
        def loss(v):
            y = eng.allgather(v, "x", algorithm="ring", segments=segments)
            return (y ** 2).sum()
        return loss

    grads = {}
    for segments in (1, 3):
        g = jax.jit(jax.shard_map(
            jax.grad(make_loss(segments)), mesh=mesh, in_specs=P("x"),
            out_specs=P("x"), check_vma=False))
        grads[segments] = np.asarray(g(jnp.asarray(X.reshape(8, -1))))
    np.testing.assert_allclose(grads[3], grads[1], atol=1e-5)


# -- streaming fusions --------------------------------------------------------

def test_allgather_matmul_segmented(eng8, rng):
    eng, mesh = eng8
    x = rng.normal(size=(8 * 4, 3)).astype(np.float32)
    w = rng.normal(size=(3, 5)).astype(np.float32)
    outs = {}
    for segments in (1, 2, 4):
        g = jax.jit(jax.shard_map(
            lambda a, b, s=segments: eng.allgather_matmul(a, b, "x",
                                                          segments=s),
            mesh=mesh, in_specs=(P("x"), P()), out_specs=P(),
            check_vma=False))
        outs[segments] = np.asarray(g(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_allclose(outs[1], x @ w, atol=1e-4)
    np.testing.assert_array_equal(outs[2], outs[1])
    np.testing.assert_array_equal(outs[4], outs[1])


def test_matmul_reduce_scatter_segmented(eng8, rng):
    eng, mesh = eng8
    x = rng.normal(size=(16, 8 * 4)).astype(np.float32)
    w = rng.normal(size=(8 * 4, 6)).astype(np.float32)
    outs = {}
    for segments in (1, 2):
        g = jax.jit(jax.shard_map(
            lambda a, b, s=segments: eng.matmul_reduce_scatter(a, b, "x",
                                                               segments=s),
            mesh=mesh, in_specs=(P(None, "x"), P("x")), out_specs=P("x"),
            check_vma=False))
        outs[segments] = np.asarray(g(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_allclose(outs[1], x @ w, atol=1e-4)
    np.testing.assert_array_equal(outs[2], outs[1])


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_segmented(eng8, rng, causal):
    eng, mesh = eng8
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = rng.normal(size=(B, S, H, hd)).astype(np.float32)
    k = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
    v = rng.normal(size=(B, S, KV, hd)).astype(np.float32)
    outs = {}
    for segments in (1, 2):
        g = jax.jit(jax.shard_map(
            lambda a, b, c, s=segments: eng.ring_attention(
                a, b, c, "x", causal=causal, segments=s),
            mesh=mesh,
            in_specs=(P(None, "x"), P(None, "x"), P(None, "x")),
            out_specs=P(None, "x"), check_vma=False))
        outs[segments] = np.asarray(
            g(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    # online softmax is exact under any block split — only rounding differs
    np.testing.assert_allclose(outs[2], outs[1], atol=2e-5)


# -- tree_allreduce buckets ---------------------------------------------------

def test_tree_allreduce_dtype_buckets_no_upcast(eng8, rng):
    """bf16 leaves must ride the wire in bf16 (dtype-grouped buckets)."""
    eng, mesh = eng8
    trees = [{"a": rng.normal(size=(4, 3)).astype(np.float32),
              "b": rng.normal(size=(8,)).astype(np.float32),
              "c": (rng.normal(size=(6,)) / 8).astype(jnp.bfloat16)}
             for _ in range(8)]
    stacked = {k: np.stack([np.asarray(t[k], np.float32) for t in trees])
               for k in trees[0]}
    eng.trace_log.clear()
    g = jax.jit(jax.shard_map(
        lambda t: jax.tree.map(
            lambda l: l[None],
            eng.tree_allreduce(jax.tree.map(lambda a: a[0], t), ("x",))),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    out = g({k: jnp.asarray(v, (jnp.bfloat16 if k == "c" else jnp.float32))
             for k, v in stacked.items()})
    assert np.asarray(out["c"]).dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out["a"])[0],
                               stacked["a"].sum(0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(out["c"], np.float32)[0],
                               stacked["c"].sum(0), rtol=0.05, atol=0.05)
    # fp32 and bf16 leaves must not share a fused buffer: the engine issued
    # at least two collectives (one per dtype bucket)
    assert len(eng.trace_log) >= 2


def test_tree_allreduce_size_cap_splits_buckets(eng8, rng):
    eng, mesh = eng8
    trees = [[rng.normal(size=(256,)).astype(np.float32) for _ in range(4)]
             for _ in range(8)]
    stacked = [np.stack([t[i] for t in trees]) for i in range(4)]
    eng.trace_log.clear()
    g = jax.jit(jax.shard_map(
        lambda t: jax.tree.map(
            lambda l: l[None],
            eng.tree_allreduce(jax.tree.map(lambda a: a[0], t), ("x",),
                               bucket_bytes=2 * 256 * 4)),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    out = g([jnp.asarray(s) for s in stacked])
    for i in range(4):
        np.testing.assert_allclose(np.asarray(out[i])[0],
                                   stacked[i].sum(0), atol=1e-4)
    # 4 leaves x 1 KiB with a 2 KiB cap -> 2 buckets -> 2 collectives
    assert len(eng.trace_log) == 2


# -- the pipelined alpha-beta model -------------------------------------------

def test_fit_segments_divisor_clamp():
    assert _fit_segments(24, 8) == 8
    assert _fit_segments(24, 16) == 12  # largest divisor <= 16
    assert _fit_segments(6, 4) == 3
    assert _fit_segments(7, 4) == 1
    assert _fit_segments(5, 1) == 1
    assert _fit_segments(0, 4) == 1


#: (collective, algorithm, cross-step streams at some k > 1) on 8 ranks:
#: the rings and the SEL_RANGE-chained recursive halving/doubling stream,
#: the unrolled trees, the hypercube allreduce and Bruck's masked
#: all-to-all are SEG_LOOP-only (serialized)
SEGMENTED_PAIRS = [
    ("allgather", "ring", True),
    ("allgather", "recursive_doubling", True),
    ("allreduce", "ring", True),
    ("allreduce", "bidi_ring", True),
    ("allreduce", "halving_doubling", True),
    ("allreduce", "recursive_doubling", False),
    ("alltoall", "bruck", False),
    ("bcast", "binomial_tree", False),
    ("reduce", "binomial_tree", False),
    ("reduce", "ring", True),
    ("reduce_scatter", "recursive_halving", True),
    ("reduce_scatter", "ring", True),
]


def _streams(prog):
    return any(isinstance(op, (Stream, StreamChain)) for op in prog.ops)


@pytest.mark.parametrize("nbytes", [1 << 20, 4 << 20, 16 << 20, 64 << 20,
                                    256 << 20])
@pytest.mark.parametrize("collective,algorithm,streams", SEGMENTED_PAIRS)
def test_pipelining_dominates_unsegmented_at_1mib(collective, algorithm,
                                                  streams, nbytes):
    """Acceptance, split-model form: for >= 1 MiB some k > 1 strictly
    beats k = 1 EXACTLY where the compiled program cross-step streams
    (`STREAM`/`STREAM_CHAIN`); a serialized program's best count is 1.
    Priced on the compiled, stream-fused programs (`Program.cost`) over
    the selector's segment ladder."""
    comm = Communicator(axis="x", size=8)
    sched = _gen_schedule(collective, algorithm, comm)
    progs = {k: sched.compile(segments=k)
             for k in Selector.DEFAULT_SEGMENT_CANDIDATES}
    assert not _streams(progs[1])
    assert any(_streams(p) for p in progs.values()) == streams
    times = {k: p.cost(nbytes, comm) for k, p in progs.items()}
    if streams:
        assert min(times.values()) < times[1]
    else:
        assert min(times, key=times.get) == 1


def test_program_cost_segment_model_shape():
    """(S + k - 1) * t_seg for a homogeneous ring stream; k=1 reduces to
    the legacy per-step sum. The model moved onto the compiled program
    (`Program.cost`) but its shape is unchanged — the golden parity test
    in test_program_cost.py pins the full surface."""
    comm = Communicator(axis="x", size=8)
    sched = A.ring_reduce_scatter(comm)
    S = sched.n_steps()
    B, alpha, bw = 8 << 20, comm.hop_latency, comm.link_bw
    legacy = sum(alpha + B * s.bytes_frac / bw for s in sched.steps)
    assert sched.compile(segments=1).cost(B, comm) == pytest.approx(legacy)
    k = 4
    t_seg = alpha + (B / 8) / (k * bw)
    assert sched.compile(segments=k).cost(B, comm) == pytest.approx(
        (S + k - 1) * t_seg)
    with pytest.raises(ValueError):
        sched.compile(segments=0)


def test_unstreamable_copy_collectives_never_auto_segment():
    """bcast trees mask receivers — no cross-step stream, so
    segmentation would only add per-segment alpha and the selector must
    not pick it. Ring allgather STREAMS and linear all-to-all CHAINS
    (immutable relay='original' payloads), so both may auto-segment
    (see test_stream_fusion); tuning can still pin any count."""
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    c = sel.choose("bcast", 64 << 20, comm)
    assert c.segments == 1, c
    assert sel.choose("alltoall", 64 << 20, comm).segments > 1
    assert sel.choose("allgather", 64 << 20, comm).segments > 1
    sel.set_tuning("allgather", "ring", segments=4)
    assert sel.choose("allgather", 64 << 20, comm).segments == 4


def test_selector_picks_segments_for_large_messages():
    sel = Selector()
    comm = Communicator(axis="x", size=8)
    big = sel.choose("allreduce", 64 << 20, comm)
    assert big.segments > 1
    assert big.schedule.segments == big.segments
    small = sel.choose("allreduce", 1024, comm)
    assert small.segments == 1  # below the Rx-buffer floor
