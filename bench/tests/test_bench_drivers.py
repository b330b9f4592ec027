"""Each driver through a whole run at a tiny size on the CPU's virtual
devices, the look for a chip skipped: the last line of stdout is the
contract's object, and `correct` holds on sound runs."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run
from bench.tests import tiny

SEED = 2**33 + 12345   # more than 32 bits hold, as a check's seeds may be


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("workload,chips", [
    ("dlrm1.b256-zipf", 1), ("dlrm1.b32-uniform", 1),
    ("dlrm4.b256-zipf", 4), ("coll.fig10-4chip", 4)])
@pytest.mark.parametrize("trace", [0, 1])
def test_run_prints_the_result_line(root, capsys, workload, chips, trace):
    rc = run.main(tiny.argv(workload, SEED, trace), root=root, src=tiny.SRC,
                  require_chip=False, cache=False)
    assert rc == 0
    r = _last_line(capsys)
    assert list(r)[:3] == ["correct", "attempted", "failed"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["count"] == chips
    assert {"platform", "kind", "memory_peak_bytes"} <= set(r["device"])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[group] if run.applies(m, workload)}
    # no device trace on the CPU: per-layer metrics read from it are left
    # out, never reported as 0
    assert set(r["metrics"]) <= names
    if trace:
        assert set(r["metrics"]) == {"compile_s"}
    else:
        assert set(r["metrics"]) == names
        assert all(m["value"] > 0 for m in r["metrics"].values())
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


def test_coll_grid_on_one_device(tmp_path):
    """The collective driver also runs on a single rank, where every
    engine call is a no-op and each chain is the oracle's."""
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "ring4-fig10.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(ranks=1, root=0, mesh={"shape": [1], "axes": ["x"]})
    tiny.write_json(path, cfg)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        w["chips"] = 1
    tiny.write_json(os.path.join(root, "BENCHMARK.json"), bench)
    r = tiny.run_cell(root, "coll.fig10-4chip", SEED)
    assert r["correct"] is True and r["device"]["count"] == 1


def test_same_seed_same_traffic(root):
    from bench import traffic
    s = traffic.id_sampler(1000, 4, "zipf", 1.05)
    keys = traffic.id_keys(SEED, 4)

    def pool(keys, **kw):
        p = traffic.IdPool(s, keys, 5, 8, 4, **kw)
        return np.stack([p[i] for i in range(len(p))])
    a, b = pool(keys), pool(keys)
    c = pool(traffic.id_keys(SEED + 1, 4))
    # made in chunks of two batches, the same batches
    assert (pool(keys, chunk_bytes=2 * 8 * 4 * 4)[:5] == a).all()
    assert (a == b).all() and not (a == c).all()
    assert len({x.tobytes() for x in a}) == len(a)   # no batch repeats
    assert a.min() >= 0 and a.max() < 1000


def test_refuses_the_cpu(root, capsys):
    rc = run.main(tiny.argv("dlrm1.b256-zipf", SEED), root=root,
                  src=tiny.SRC, cache=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_device_check():
    v5e = _Dev("tpu", "TPU v5 lite")
    info = run.device_info([v5e] * 4, 4, require_chip=True)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    with pytest.raises(run.BenchError, match="peaks"):
        run.device_info([_Dev("tpu", "TPU v9 imaginary")], 1, True)
    with pytest.raises(run.BenchError, match="no TPU"):
        run.device_info([_Dev("cpu", "cpu")], 1, True)
    with pytest.raises(run.BenchError, match="needs 4 chips"):
        run.device_info([v5e], 4, True)


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ measures
    nothing: non-zero exit, no result."""
    root = tiny.make_root(str(tmp_path))
    p = subprocess.run([sys.executable, os.path.join(root, "bench", "run.py")]
                       + tiny.argv("dlrm1.b256-zipf", SEED),
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout == ""
