"""The reduction from trace to metrics and the classification of device
operations: on a hand-made trace with known answers, on a small HLO text,
and on an excerpt recorded from a chip run."""
import json
import os

import pytest

from bench import opclass, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule jit_dlrm_serve_step, entry_computation_layout={}

%fused_gather (param_0: f32[4,8,2], param_1: s32[6]) -> f32[6,2] {
  %param_0 = f32[4,8,2]{2,1,0} parameter(0)
  ROOT %g = f32[6,2]{1,0} gather(%param_0, %param_1), slice_sizes={1,1,2}
}

%body (p: (f32[6,2], s32[])) -> (f32[6,2], s32[]) {
  %p = (f32[6,2]{1,0}, s32[]) parameter(0)
  %gte = f32[6,2]{1,0} get-tuple-element(%p), index=0
  %cp = f32[6,2]{1,0} collective-permute(%gte), source_target_pairs={{0,1}}
  %add.1 = f32[6,2]{1,0} add(%gte, %cp)
  ROOT %t = (f32[6,2]{1,0}, s32[]) tuple(%add.1, %gte)
}

ENTRY %main (params.1: f32[4,8,2], params.2: f32[4,3], ids.1: s32[6]) -> f32[2,3] {
  %params.1 = f32[4,8,2]{2,1,0} parameter(0), metadata={op_name="params['tables']"}
  %params.2 = f32[4,3]{1,0} parameter(1), metadata={op_name="params['fc'][0]['w']"}
  %ids.1 = s32[6]{0} parameter(2), metadata={op_name="ids"}
  %fusion = f32[6,2]{1,0} fusion(%params.1, %ids.1), kind=kCustom, calls=%fused_gather
  %c = s32[] constant(0)
  %tup = (f32[6,2]{1,0}, s32[]) tuple(%fusion, %c)
  %while.1 = (f32[6,2]{1,0}, s32[]) while(%tup), condition=%cond, body=%body
  %gte.2 = f32[6,2]{1,0} get-tuple-element(%while.1), index=0
  %bitcast.3 = f32[3,4]{1,0} bitcast(%gte.2)
  ROOT %dot.4 = f32[3,3]{1,0} dot(%bitcast.3, %params.2), lhs_contracting_dims={1}
}
"""


def test_classify_by_data_flow():
    c = opclass.classify(HLO, opclass.dlrm_seed_tags)
    assert c["module"] == "jit_dlrm_serve_step"
    cls = c["classes"]
    assert cls["fusion"] == "lookup"
    assert cls["cp"] == "collective"
    assert cls["add.1"] == "lookup"        # the combine inside the loop
    assert cls["dot.4"] == "fc"
    assert cls["c"] == "other"


def test_instr_name_of_a_trace_event():
    name = ("%fusion.14 = s32[100,256,1]{1,0,2:T(8,128)S(1)} fusion(s32[256,"
            "100]{0,1:T(8,128)} %i.1), kind=kLoop, calls=%fused_computation")
    assert opclass.instr_name(name) == "fusion.14"


def _hand_trace():
    # window [0, 100) ns; two program runs; ops cover 10-30 and 50-60
    ops = [["%a = f32[] fusion()", 10, 15], ["%b = f32[] dot()", 20, 10],
           ["%a = f32[] fusion()", 50, 10]]
    return {
        "devices": {"0": {"modules": [["jit_m(1)", 5, 30], ["jit_m(1)", 45,
                                                               20]],
                          "ops": ops}},
        "host": [["bench_window", 0, 100], ["ids_to_device", 0, 10],
                 ["dispatch", 30, 12], ["wait", 42, 8], ["wait", 60, 40]],
    }


def test_reduce_hand_trace():
    r = trace.reduce(_hand_trace(), {"jit_m": {"a": "lookup", "b": "fc"}})
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)     # [10,30) and [50,60)
    assert r["classes_s"]["lookup"] == pytest.approx(25e-9)
    assert r["classes_s"]["fc"] == pytest.approx(10e-9)
    assert r["modules"]["jit_m"]["count"] == 2
    assert r["modules"]["jit_m"]["span_s"] == pytest.approx(50e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["host: ids_to_device"] == pytest.approx(10e-9)
    # the gap [30, 50) overlaps dispatch most; [60, 100) is all wait
    assert gaps["host: dispatch"] == pytest.approx(20e-9)
    assert gaps["host: wait"] == pytest.approx(40e-9)
    top = r["breakdown"]["device_ops"][0]
    assert top[0] == "jit_m:a [lookup]" and top[1] == pytest.approx(25e-9)


def test_reduce_without_device_ops_reads_nothing():
    t = _hand_trace()
    t["devices"] = {}
    assert trace.reduce(t, {}) is None


def test_recorded_chip_excerpt():
    """An excerpt of a traced one-chip DLRM window (TPU v5e): the lookup
    dominates the device time, FC is a small share, and the numbers
    reproduce the ones recorded with it."""
    with open(os.path.join(DATA, "dlrm1_trace_excerpt.json")) as f:
        rec = json.load(f)
    r = trace.reduce(rec["trace"], rec["classes"])
    want = rec["reduced"]
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["window_s"] == pytest.approx(want["window_s"])
    for k, v in want["classes_s"].items():
        assert r["classes_s"][k] == pytest.approx(v)
    assert r["classes_s"]["lookup"] > 5 * r["classes_s"]["fc"]
    assert 0 < r["busy_s"] < r["window_s"]
    assert set(r["modules"]) == {"jit_dlrm_serve_step"}
