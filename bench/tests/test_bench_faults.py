"""The comparison that decides `correct` fails what it must: each cell's
control (the reference at the precision below the configuration's, in the
program's place) and each fault a cell can have, planted in the timed
path underneath a whole run."""
import jax
import jax.numpy as jnp
import pytest

from bench import control
from bench.tests import tiny

SEED = 2**33 + 777


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload", ["dlrm1.b256-zipf", "dlrm1.b32-uniform",
                                      "dlrm4.b256-zipf", "coll.fig10-4chip"])
def test_control_is_not_correct(root, workload):
    r = tiny.run_cell(root, workload, SEED, patch=control.install)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def _alter_logit(monkeypatch):
    from repro.models import dlrm as dm
    forward = dm.dlrm_forward

    def altered(params, ids, ctx, use_pallas=False):
        return forward(params, ids, ctx, use_pallas).at[0, 0].add(0.5)
    monkeypatch.setattr(dm, "dlrm_forward", altered)


def _half_batch(monkeypatch):
    from repro.models import dlrm as dm
    forward = dm.dlrm_forward

    def half(params, ids, ctx, use_pallas=False):
        h = ids.shape[0] // 2
        y = forward(params, ids[:h], ctx, use_pallas)
        rest = jnp.broadcast_to(y.mean(0), (ids.shape[0] - h, y.shape[1]))
        return jnp.concatenate([y, rest])
    monkeypatch.setattr(dm, "dlrm_forward", half)


def _no_exchange(monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)


def _alter_bcast(monkeypatch):
    from repro.core import CollectiveEngine
    bcast = CollectiveEngine.bcast

    def altered(self, x, axis, root=0, algorithm="auto"):
        return bcast(self, x, axis, root=root, algorithm=algorithm) \
            .at[0].add(1.0)
    monkeypatch.setattr(CollectiveEngine, "bcast", altered)


@pytest.mark.parametrize("workload,fault", [
    ("dlrm1.b256-zipf", _alter_logit),
    ("dlrm1.b256-zipf", _half_batch),
    ("dlrm4.b256-zipf", _alter_logit),
    ("dlrm4.b256-zipf", _half_batch),
    ("dlrm4.b256-zipf", _no_exchange),
    ("coll.fig10-4chip", _no_exchange),
    ("coll.fig10-4chip", _alter_bcast),
])
def test_fault_is_not_correct(root, monkeypatch, workload, fault):
    fault(monkeypatch)
    r = tiny.run_cell(root, workload, SEED)
    assert r["correct"] is False


def test_dlrm_control_is_three_bfloat16_passes():
    """The DLRM control's matmul lies between float32 and one bfloat16
    pass, as `high` does: above float32's error, far below bf16's."""
    import numpy as np
    rng = np.random.default_rng(3)
    a = rng.uniform(-1, 1, (64, 512)).astype(np.float32)
    b = rng.uniform(-1, 1, (512, 64)).astype(np.float32)
    want = a.astype(np.float64) @ b.astype(np.float64)

    def err(x):
        return float(np.abs(np.asarray(x, np.float64) - want).max())
    f32 = err(jnp.dot(a, b, precision="highest"))
    bf16 = err(jnp.dot(jnp.asarray(a, jnp.bfloat16),
                       jnp.asarray(b, jnp.bfloat16),
                       preferred_element_type=jnp.float32))
    high = err(control.dot_high(jnp.asarray(a), jnp.asarray(b)))
    assert 3 * f32 < high < bf16 / 100
