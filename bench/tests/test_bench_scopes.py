"""The program's device scopes and their reduction (`bench/scopes.py`):
the tiny DLRM step compiles to HLO whose op_names carry the scopes and
whose results are those of the step traced without them; the reduction
on hand-made events with known answers; and on an excerpt recorded from
a chip run."""
import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bench import scopes
from bench.tests import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _dlrm_step(tp: int):
    """The tiny `dlrm-table2` step with four FC layers, tables sharded
    over a `tp`-way model axis of the CPU's devices: (jitted step,
    params, ids)."""
    from repro.configs.base import ParallelConfig
    from repro.configs.dlrm import DLRMConfig
    from repro.core import CollectiveEngine
    from repro.core.topology import make_mesh
    from repro.models import dlrm as dm
    from repro.parallel.ops import ParCtx

    c = tiny.CONFIGS["dlrm-table2"]
    cfg = DLRMConfig(n_tables=c["n_tables"], emb_dim=c["emb_dim"],
                     rows_per_table=c["rows_per_table"],
                     fc_dims=(64, 32, 16), out_dim=c["out_dim"])
    mesh = make_mesh((1, 1, tp), ("pod", "data", "model"))
    ctx = ParCtx(engine=CollectiveEngine(mesh), pcfg=ParallelConfig(),
                 mesh=mesh)
    params = dm.dlrm_init(cfg, mesh, seed=3)
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(0, cfg.rows_per_table,
                                   (8, cfg.n_tables)).astype(np.int32))
    step = jax.jit(jax.shard_map(
        lambda p, i: dm.dlrm_forward(p, i, ctx), mesh=mesh,
        in_specs=(dm.dlrm_specs(cfg, tp), P(None, None)),
        out_specs=P(None, None), check_vma=False))
    return step, params, ids


def _strip_metadata(text: str) -> str:
    """HLO text without its metadata and source-location tables."""
    lines = [ln for ln in text.splitlines() if not re.match(
        r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", ln)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.mark.parametrize("tp", [1, 4])
def test_dlrm_step_carries_its_scopes(tp, monkeypatch):
    step, params, ids = _dlrm_step(tp)
    text = step.lower(params, ids).compile().as_text()
    out = np.asarray(step(params, ids))
    names = set()
    for path in scopes.scope_map(text).values():
        names |= set(path)
    want = {"dlrm.lookup", "dlrm.fc0", "dlrm.fc1", "dlrm.fc2", "dlrm.fc3"}
    if tp > 1:
        want |= {"engine.allreduce", "engine.allgather"}
        assert any(n.startswith("algo.") for n in names)
        assert any(n.startswith("uop.") for n in names)
    assert want <= names

    # the same step traced with every scope a no-op: the scopes are
    # metadata, so the program and its results are the same bit for bit
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, params, ids = _dlrm_step(tp)
    plain_text = plain.lower(params, ids).compile().as_text()
    assert not any(scopes.scope_map(plain_text).values())
    assert _strip_metadata(plain_text) == _strip_metadata(text)
    np.testing.assert_array_equal(np.asarray(plain(params, ids)), out)


HLO = """HloModule jit_step, entry_computation_layout={}

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %cp = f32[4]{0} collective-permute(%p), source_target_pairs={{0,1}}, metadata={op_name="jit(step)/dlrm.lookup/engine.allreduce/algo.ring/uop.loop/while/body/ppermute"}
  ROOT %add.1 = f32[4]{0} add(%p, %cp), metadata={op_name="jit(step)/dlrm.lookup/engine.allreduce/algo.ring/uop.loop/uop.combine/add"}
}

ENTRY %main (ids.1: s32[4], w.2: f32[4,4]) -> f32[4] {
  %ids.1 = s32[4]{0} parameter(0), metadata={op_name="ids"}
  %w.2 = f32[4,4]{1,0} parameter(1)
  %fusion = f32[4]{0} fusion(%ids.1), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/dlrm.lookup/mul"}
  %copy.3 = f32[4]{0} copy(%fusion)
  %while.1 = f32[4]{0} while(%copy.3), condition=%cond, body=%body, metadata={op_name="jit(step)/dlrm.lookup/engine.allreduce/algo.ring/uop.loop/while"}
  ROOT %dot.4 = f32[4]{0} dot(%while.1, %w.2), lhs_contracting_dims={0}, metadata={op_name="jit(step)/dlrm.fc0/dot_general"}
}
"""


def test_scope_map_of_hlo_text():
    m = scopes.scope_map(HLO)
    assert m["fusion"] == ("dlrm.lookup",)
    assert m["dot.4"] == ("dlrm.fc0",)
    assert m["while.1"] == ("dlrm.lookup", "engine.allreduce", "algo.ring",
                            "uop.loop")
    assert m["add.1"][-1] == "uop.combine"
    assert m["copy.3"] == ()                  # XLA's own copy: unscoped
    assert scopes.owner(m["add.1"]) == "engine.allreduce"
    assert scopes.owner(m["fusion"]) == "dlrm.lookup"
    assert scopes.owner(m["copy.3"]) == ""


U = 10_000   # ns: the hand trace's unit, 10 us


def _hand_trace():
    # window [0, 100U), one program run [5U, 70U); the while [20U, 40U)
    # holds two nested ops [22U, 30U) and [28U, 36U). Idle: [0, 10U),
    # [40U, 50U), [65U, 100U). The host's clock reads 30U (300 us) ahead.
    ops = [["%fusion = f32[4] fusion()", 10, 10],
           ["%while.1 = f32[4] while()", 20, 20],
           ["%cp = f32[4] collective-permute()", 22, 8],
           ["%add.1 = f32[4] add()", 28, 8],
           ["%dot.4 = f32[4] dot()", 50, 10],
           ["%copy.3 = f32[4] copy()", 60, 5]]
    host = [["wait", 25, 20],                 # the benchmark's annotation
            ["PjRtExecute", 70, 10],          # the idle [40U, 50U)
            ["Launch", 68, 22],               # around it, longer
            ["TransferToDevice", 95, 30],     # [65U, 95U) of the last gap
            ["HostWait", 95, 35],             # all of it, but longer
            ["ThreadLoop", -100, 400]]        # the whole window: no label
    scale = lambda evs: [[n, s * U, d * U] for n, s, d in evs]  # noqa: E731
    return {"devices": {"0": {"modules": scale([["jit_step(7)", 5, 65]]),
                              "ops": scale(ops)}},
            "host": scale(host), "window": [0, 100 * U]}


def test_reduce_hand_trace():
    r = scopes.reduce(_hand_trace(), {"jit_step": scopes.scope_map(HLO)})
    own = {k: v / U * 1e9 for k, v in r["owners"]["jit_step"].items()}
    # the while and its nested ops are one interval [20U, 40U)
    assert own["engine.allreduce"] == pytest.approx(20)
    # engine time inside dlrm.lookup is left out of lookup
    assert own["dlrm.lookup"] == pytest.approx(10)
    assert own["dlrm.fc0"] == pytest.approx(10)
    assert own[""] == pytest.approx(5)
    assert scopes.owner_s(r, "dlrm.fc") == pytest.approx(10 * U * 1e-9)
    assert scopes.owner_s(r, "engine.", ["jit_other"]) is None
    assert r["paths"]["dlrm.lookup/engine.allreduce/algo.ring/uop.loop"] \
        == pytest.approx(20 * U * 1e-9)
    # the runtime's events cover the idle time best 300 us back
    assert r["clock_shift_us"] == [300.0]
    causes = {k: v / U * 1e9 for k, v in r["idle_causes"]}
    # each idle instant goes to the shortest event open then: [0, 10U)
    # to the annotation alone, [40U, 50U) to PjRtExecute within Launch,
    # [65U, 95U) to TransferToDevice within HostWait, the rest HostWait
    assert causes == pytest.approx({"host: wait": 10, "PjRtExecute": 10,
                                    "TransferToDevice": 30, "HostWait": 5})


def test_idle_without_host_events():
    t = _hand_trace()
    t["host"] = []
    r = scopes.reduce(t, {})
    assert r["clock_shift_us"] == [0.0]
    assert r["idle_causes"] == [[scopes.NO_EVENT, pytest.approx(55 * U
                                                                * 1e-9)]]


def test_reduce_without_a_window_or_device_ops():
    t = _hand_trace()
    t["window"] = None
    assert scopes.reduce(t, {}) is None
    t = _hand_trace()
    t["devices"] = {}
    assert scopes.reduce(t, {}) is None


def test_readings_need_their_scopes():
    """A program compiled without the scopes reads nothing, and nothing
    is reported as 0."""
    r = scopes.reduce(_hand_trace(), {})
    ctx = {"trace": {"modules": {"jit_step": {"count": 1}}},
           "window": {"batches": 2}, "program_s": {},
           "work": {"chain": 8, "programs": [
               {"module": "jit_step", "cls": "small"}]}}
    assert scopes.readings(r, ctx) == {}
    r = scopes.reduce(_hand_trace(), {"jit_step": scopes.scope_map(HLO)})
    ctx["program_s"] = {"/repro/engine/choose_duration": 1.5,
                        "/repro/engine/compile_duration": 0.25}
    got = scopes.readings(r, ctx)
    ns = U * 1e-9
    assert got["lookup_ms.scope"] == pytest.approx(10 * ns / 2 * 1e3)
    assert got["fc_ms.scope"] == pytest.approx(10 * ns / 2 * 1e3)
    assert got["coll_engine_us.small"] == pytest.approx(20 * ns / 8 * 1e6)
    assert got["engine_setup_s"] == pytest.approx(1.75)


@pytest.mark.parametrize("workload", ["dlrm1.b32-uniform",
                                      "coll.fig10-4chip"])
def test_traced_run_with_scopes(tmp_path, capsys, workload):
    """`python3 -m bench.scopes` at a tiny size on the CPU: the run's
    result line with the scopes added. The CPU's profile has no device
    plane, so only the set-up reading is there to read, and only where
    the engine worked: the one-chip DLRM step makes no engine call."""
    root = tiny.make_root(str(tmp_path))
    rc = scopes.main(tiny.argv(workload, 2**33 + 7), root=root,
                     src=tiny.SRC, require_chip=False, cache=False)
    assert rc == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"] is True
    sc = r["scopes"]
    dlrm = workload.startswith("dlrm")
    assert set(sc["metrics"]) == (set() if dlrm else {"engine_setup_s"})
    assert all(v > 0 for v in sc["metrics"].values())
    assert all(k.startswith("/repro/engine/") for k in sc["program_s"])
    assert sc["paths"] == [] and sc["idle_causes"] == []
    assert set(sc["traced_end_to_end"]) == (
        {"dlrm_qps", "dlrm_p95_ms"} if dlrm
        else {"coll_small_us", "coll_large_GBps"})


def test_recorded_chip_excerpt():
    """12 ms of a traced one-chip DLRM window (TPU v5e) with the scope
    maps of its program: the lookup is most of the device time and
    holds no engine call at tp=1, and the scope times, the clock shift
    and the idle causes reproduce the ones recorded with it."""
    with open(os.path.join(DATA, "scopes_excerpt.json")) as f:
        rec = json.load(f)
    maps = {m: {k: tuple(v) for k, v in ins.items()}
            for m, ins in rec["maps"].items()}
    r = scopes.reduce(rec["trace"], maps)
    want = rec["reduced"]
    for mod, owners in want["owners"].items():
        assert r["owners"][mod] == pytest.approx(owners)
    own = r["owners"]["jit_dlrm_serve_step"]
    assert own["dlrm.lookup"] > 5 * sum(v for k, v in own.items()
                                        if k.startswith("dlrm.fc"))
    assert not any(k.startswith("engine.") for k in own)
    assert r["clock_shift_us"] == want["clock_shift_us"]
    assert dict(r["idle_causes"]) == pytest.approx(
        dict(want["idle_causes"]))
