"""A later PR adds a configuration, a traffic mix and a per-layer metric
as new files plus entries in BENCHMARK.json, editing no file that is
there: the harness finds them by name and runs the new cell."""
import json
import os

from bench.tests import tiny

READER = '''"""Queries served in the traced window (a count)."""


def read(ctx):
    return ctx["window"]["batches"] * ctx["work"]["batch"]
'''


def test_new_config_mix_and_metric_from_files(tmp_path):
    root = tiny.make_root(str(tmp_path))
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(os.path.join(root, "bench"))
              for p in fs}
    cfg = dict(tiny.CONFIGS["dlrm-table2-shard"], name="dlrm-narrow",
               n_tables=3, rows_per_table=640, fc_dims=[48],
               check={"logit_gap_limit": 0.02})
    tiny.write_json(os.path.join(root, "bench", "configs",
                                 "dlrm-narrow.json"), cfg)
    mix = dict(tiny.MIXES["b32-uniform"], batch=16)
    tiny.write_json(os.path.join(root, "bench", "traffic", "b16-new.json"),
                    mix)
    with open(os.path.join(root, "bench", "metrics", "queries_traced.py"),
              "w") as f:
        f.write(READER)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "dlrm1.b16-new"
    bench["configs"].append({"name": "dlrm-narrow", "source": "test",
                             "file": "bench/configs/dlrm-narrow.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "dlrm-narrow",
                               "traffic": "b16-new", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "dlrm_qps" in m["name"] or "dlrm_p95" in m["name"]:
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": "queries_traced", "unit": "queries",
                               "better": "higher", "source": "host_clock",
                               "layer": "model step", "moves": "dlrm_qps",
                               "workloads": [cell]})
    tiny.write_json(os.path.join(root, "BENCHMARK.json"), bench)

    r = tiny.run_cell(root, cell, 4242)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"dlrm_qps", "dlrm_p95_ms", "setup_s"}
    r = tiny.run_cell(root, cell, 4243, trace=1)
    assert r["correct"] is True
    assert r["metrics"]["queries_traced"]["value"] == r["attempted"]
    for p, t in before.items():
        for d, _, fs in os.walk(os.path.join(root, "bench")):
            if p in fs:
                assert os.path.getmtime(os.path.join(d, p)) == t
