"""Schedule generators vs numpy oracles in the rank simulator, plus
hypothesis property tests on schedule structure."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import algorithms as A
from repro.core.simulator import oracle, simulate
from repro.core.topology import Communicator


def _inputs(rng, n, chunks, width=3):
    return [rng.normal(size=(chunks * 2, width)).astype(np.float32)
            for _ in range(n)]


@pytest.mark.parametrize("n", [2, 3, 4, 5, 8, 16])
def test_ring_allreduce(rng, n):
    comm = Communicator(axis="x", size=n)
    xs = _inputs(rng, n, n)
    out = simulate(A.ring_allreduce(comm), xs)
    ref = oracle("allreduce", xs)
    for r in range(n):
        np.testing.assert_allclose(out[r], ref, atol=1e-4)


@pytest.mark.parametrize("n", [2, 4, 8, 16])
def test_bidi_ring_allreduce(rng, n):
    comm = Communicator(axis="x", size=n)
    xs = _inputs(rng, n, 2 * n)
    out = simulate(A.bidi_ring_allreduce(comm), xs)
    ref = oracle("allreduce", xs)
    for r in range(n):
        np.testing.assert_allclose(out[r], ref, atol=1e-4)


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("gen,coll", [
    (A.recursive_doubling_allreduce, "allreduce"),
    (A.halving_doubling_allreduce, "allreduce"),
])
def test_hypercube_allreduce(rng, n, gen, coll):
    comm = Communicator(axis="x", size=n)
    xs = _inputs(rng, n, n)
    out = simulate(gen(comm), xs)
    ref = oracle(coll, xs)
    for r in range(n):
        np.testing.assert_allclose(out[r], ref, atol=1e-4)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_recursive_halving_rs(rng, n):
    comm = Communicator(axis="x", size=n)
    xs = _inputs(rng, n, n)
    sched = A.recursive_halving_reduce_scatter(comm)
    out = simulate(sched, xs)
    ref = oracle("reduce_scatter", xs)
    c = xs[0].shape[0] // n
    for r in range(n):
        np.testing.assert_allclose(out[r][r * c:(r + 1) * c],
                                   ref[r * c:(r + 1) * c], atol=1e-4)


@pytest.mark.parametrize("n", [2, 3, 5, 8])
@pytest.mark.parametrize("root", [0, 1])
@pytest.mark.parametrize("gen", [A.binomial_tree_bcast, A.one_to_all_bcast])
def test_bcast(rng, n, root, gen):
    if root >= n:
        pytest.skip("root out of range")
    comm = Communicator(axis="x", size=n)
    xs = _inputs(rng, n, 1)
    out = simulate(gen(comm, root=root), xs)
    for r in range(n):
        np.testing.assert_allclose(out[r], xs[root])


@pytest.mark.parametrize("n", [2, 3, 5, 8])
@pytest.mark.parametrize("gen", [A.ring_reduce, A.all_to_one_reduce,
                                 A.binomial_tree_reduce])
def test_reduce_root(rng, n, gen):
    comm = Communicator(axis="x", size=n)
    xs = _inputs(rng, n, 1)
    out = simulate(gen(comm, root=0), xs)
    np.testing.assert_allclose(out[0], oracle("allreduce", xs), atol=1e-4)


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("gen", [A.ring_gather, A.all_to_one_gather,
                                 A.binomial_tree_gather])
def test_gather_root(rng, n, gen):
    comm = Communicator(axis="x", size=n)
    data = [rng.normal(size=(2, 3)).astype(np.float32) for _ in range(n)]
    ins = []
    for r in range(n):
        buf = np.zeros((n * 2, 3), np.float32)
        buf[r * 2:(r + 1) * 2] = data[r]
        ins.append(buf)
    out = simulate(gen(comm, root=0), ins)
    np.testing.assert_allclose(out[0], np.concatenate(data, 0))


@pytest.mark.parametrize("n", [2, 3, 4, 8])
@pytest.mark.parametrize("gen", [A.linear_alltoall, A.bruck_alltoall])
def test_alltoall(rng, n, gen):
    if gen is A.bruck_alltoall and n & (n - 1):
        pytest.skip("bruck needs pow2")
    comm = Communicator(axis="x", size=n)
    xs = _inputs(rng, n, n)
    out = simulate(gen(comm), xs)
    refs = oracle("alltoall", xs)
    for r in range(n):
        np.testing.assert_allclose(out[r], refs[r])


# ---------------------------------------------------------------------------
# Property tests (hypothesis): structural invariants of every schedule
# ---------------------------------------------------------------------------

_POW2 = st.sampled_from([2, 4, 8, 16])
_ANY_N = st.integers(min_value=2, max_value=16)


@given(n=_POW2)
@settings(max_examples=10, deadline=None)
def test_ring_allreduce_wire_bytes_optimal(n):
    """Ring allreduce must move exactly 2(n-1)/n of the message per rank."""
    comm = Communicator(axis="x", size=n)
    sched = A.ring_allreduce(comm)
    assert abs(sched.bytes_on_wire(1.0) - 2 * (n - 1) / n) < 1e-9


@given(n=_ANY_N)
@settings(max_examples=15, deadline=None)
def test_schedules_validate(n):
    comm = Communicator(axis="x", size=n)
    gens = [A.ring_allreduce, A.ring_reduce_scatter, A.ring_allgather,
            A.binomial_tree_bcast, A.one_to_all_bcast, A.ring_reduce,
            A.all_to_one_reduce, A.binomial_tree_reduce, A.linear_alltoall]
    if n & (n - 1) == 0:
        gens += [A.recursive_doubling_allreduce, A.bruck_alltoall,
                 A.halving_doubling_allreduce, A.bidi_ring_allreduce]
    for gen in gens:
        sched = gen(comm)
        sched.validate()  # no duplicate src/dst, ranks in range
        assert sched.n_steps() >= 1


@given(n=_POW2, data=st.data())
@settings(max_examples=8, deadline=None)
def test_allreduce_linearity(n, data):
    """allreduce(a x + b y) == a allreduce(x) + b allreduce(y)."""
    comm = Communicator(axis="x", size=n)
    sched = A.ring_allreduce(comm)
    rng = np.random.default_rng(data.draw(st.integers(0, 2 ** 16)))
    xs = [rng.normal(size=(n * 2, 2)).astype(np.float32) for _ in range(n)]
    ys = [rng.normal(size=(n * 2, 2)).astype(np.float32) for _ in range(n)]
    a, b = 2.0, -0.5
    lhs = simulate(sched, [a * x + b * y for x, y in zip(xs, ys)])
    rx = simulate(sched, xs)
    ry = simulate(sched, ys)
    for r in range(n):
        np.testing.assert_allclose(lhs[r], a * rx[r] + b * ry[r],
                                   atol=1e-3)
