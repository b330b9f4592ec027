"""BENCHMARK.json against the benchmark's contract: names and units, the
metrics each cell reports, the share of four-chip cells, and every file a
cell or metric is found by."""
import json
import math
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def _cell_metrics(cell: str, group: str):
    return [m for m in BENCH[group]
            if "workloads" not in m or cell in m["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1:] == ["bench/run.py"]
    assert len(json.dumps(BENCH)) < 64 << 10


def test_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_units_and_lines(group):
    names = [e["name"] for e in BENCH[group]]
    assert len(set(names)) == len(names)
    for e in BENCH[group]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer"):
            if key in e:
                assert LINE.match(e[key]), (e["name"], key)
        if group == "configs":
            assert LINE.match(e["source"]), e["name"]
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.match(e[key])
        for key in e.get("reduced", []):
            assert NAME.match(key)


def test_end_to_end_bounds_and_setup():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert set(e2e) == {"dlrm_qps", "dlrm_p95_ms", "coll_small_us",
                        "coll_large_GBps", "setup_s"}
    assert "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_enough():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in _cell_metrics(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert _cell_metrics(w["name"], "per_layer"), w["name"]


def test_moves_is_reported_in_each_cell_of_the_metric():
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            e2e = {e["name"] for e in _cell_metrics(cell, "end_to_end")}
            assert m["moves"] in e2e, (m["name"], cell)


def test_shares_are_percent():
    for m in BENCH["per_layer"]:
        if "share" in m["name"] or "roofline" in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%", m["name"]


def test_four_chip_share():
    cells = BENCH["workloads"]
    four = sum(w["chips"] == 4 for w in cells)
    assert all(w["chips"] in (1, 4) for w in cells)
    assert four <= max(1, len(cells) // 2)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)


def test_every_named_file_exists():
    root = REPO
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = set()
    for w in BENCH["workloads"]:
        conf = configs[w["config"]]
        used.add(conf["name"])
        assert conf["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(root, conf["file"]))
        mix_path = os.path.join(root, "bench", "traffic",
                                w["traffic"] + ".json")
        with open(mix_path) as f:
            mix = json.load(f)
        assert os.path.isfile(os.path.join(root, "bench", "drivers",
                                           mix["driver"] + ".py"))
    assert used == set(configs)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(root, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]


def test_run_seconds_fits_a_full_check():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    total = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
    assert math.isfinite(total)
