"""A benchmark root in a temporary directory: the real BENCHMARK.json's
cells, metrics and harness, with tiny configuration and traffic files
under the same names, so a whole run fits a test on the CPU."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(REPO, "src")

DLRM = {"model": "dlrm", "n_tables": 4, "emb_dim": 32,
        "rows_per_table": 1000, "dense_features": 0, "fc_dims": [64, 32],
        "out_dim": 1, "dtype": "float32", "matmul_precision": "highest"}
CONFIGS = {
    "dlrm-table2-shard": dict(DLRM, mesh={"shape": [1, 1, 1],
                                          "axes": ["pod", "data", "model"]}),
    "dlrm-table2": dict(DLRM, mesh={"shape": [1, 1, 4],
                                    "axes": ["pod", "data", "model"]}),
    "ring4-fig10": {"ranks": 4, "root": 1,
                    "collectives": ["allreduce", "reduce_scatter",
                                    "allgather", "bcast", "reduce", "gather",
                                    "alltoall"],
                    "sizes_bytes": [256, 4096],
                    "mesh": {"shape": [4], "axes": ["x"]}},
}
DLRM_MIX = {"driver": "dlrm_serve", "batch": 8, "pool_batches_per_s": 100,
            "warm_batches": 2, "check_queries": 32, "trace_seconds": 0.3}
MIXES = {
    "b256-zipf": dict(DLRM_MIX, ids={"dist": "zipf", "alpha": 1.05}),
    "b32-uniform": dict(DLRM_MIX, ids={"dist": "uniform"}),
    "fig10": {"driver": "coll_grid", "chain": 8, "large_sizes": [4096],
              "phase_min_s": 0.05, "value_range": 8, "trace_seconds": 0.3},
}


# Every cell the drivers are tested in, whether or not BENCHMARK.json
# measures it on the chip.
CELLS = {
    "dlrm1.b256-zipf": ("dlrm-table2-shard", "b256-zipf", 1),
    "dlrm1.b32-uniform": ("dlrm-table2-shard", "b32-uniform", 1),
    "dlrm4.b256-zipf": ("dlrm-table2", "b256-zipf", 4),
    "coll.fig10-4chip": ("ring4-fig10", "fig10", 4),
}


def make_root(tmp: str) -> str:
    """A checkout-like root in `tmp` holding BENCHMARK.json and bench/,
    with the tiny files in place of the real ones (their limits kept)."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {c["name"] for c in bench["configs"]}
    bench["configs"] += [
        {"name": name, "source": "test", "reduced": [], "why": "test",
         "file": f"bench/configs/{name}.json"}
        for name in CONFIGS if name not in listed]
    cells = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [
        {"name": name, "config": c, "traffic": t, "chips": n, "why": "test"}
        for name, (c, t, n) in CELLS.items() if name not in cells]
    for conf in bench["configs"]:
        real = os.path.join(REPO, conf["file"])
        if not os.path.exists(real):   # a cell tested, not yet measured
            real = os.path.join(REPO, "bench", "configs",
                                "dlrm-table2-shard.json")
        with open(real) as f:
            check = json.load(f)["check"]
        write_json(os.path.join(tmp, conf["file"]),
                   dict(CONFIGS[conf["name"]], name=conf["name"],
                        check=check))
    for name, mix in MIXES.items():
        write_json(os.path.join(tmp, "bench", "traffic", name + ".json"), mix)
    write_json(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp


def write_json(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def argv(workload: str, seed: int, trace: int = 0,
         seconds: float = 0.2) -> list:
    return ["--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]


def run_cell(root: str, workload: str, seed: int, trace: int = 0,
             patch=None) -> dict:
    """One whole run on the CPU, the look for a chip skipped."""
    from bench import run
    return run.run(argv(workload, seed, trace), root=root, src=SRC,
                   require_chip=False, cache=False, patch=patch)
