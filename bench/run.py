#!/usr/bin/env python3
"""Run one benchmark cell on the chips this machine holds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name from `BENCHMARK.json`: its
configuration (`bench/configs/<config>.json`), its traffic mix
(`bench/traffic/<mix>.json`, whose `driver` names the step's driver in
`bench/drivers/`), and each per-layer metric's reader
(`bench/metrics/<metric>.py`).

The run loads the seed's data on the device, compiles and warms every
shape the cell uses (set-up), measures for `--seconds` (with `--trace 1`:
for the mix's `trace_seconds`, under the profiler), then compares what the
window served with the plain reference. The last line of stdout is one
JSON object; the numbers compared, each with its limit, are the last
lines of stderr and the last key of that object. Any device but a TPU the
peaks table lists, or fewer of them than the cell asks for, exits non-zero
before any work.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# The JAX events that make up compile time: tracing, lowering, the
# backend's compile, and loading a compiled program from the cache.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class BenchError(Exception):
    """A run that cannot measure: it prints no result and exits non-zero."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str, root: str) -> dict:
    """The cell's entry, configuration and mix, from files found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load_json(os.path.join(root, conf["file"]))
    mix = _load_json(os.path.join(root, "bench", "traffic",
                                  cell["traffic"] + ".json"))
    return {"cell": cell, "cfg": cfg, "mix": mix}


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def device_info(devices, chips: int, require_chip: bool) -> dict:
    """What the result reports of the devices; BenchError unless they are
    `chips` TPUs of a kind the peaks table lists (when `require_chip`)."""
    from bench.peaks import peaks_for
    d = devices[0]
    if require_chip:
        if d.platform != "tpu":
            raise BenchError(f"JAX found no TPU (platform {d.platform!r}); "
                             f"the benchmark runs only on a chip")
        try:
            peaks_for(d.device_kind)
        except ValueError as e:
            raise BenchError(str(e)) from None
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def matmul_precision(cfg: dict):
    """JAX's default matmul precision as the configuration states it (the
    platform's default where it states none), for everything the run
    traces: a deployment setting, like the compile cache."""
    import jax
    p = cfg.get("matmul_precision")
    return jax.default_matmul_precision(p) if p else contextlib.nullcontext()


def memory_peak(devices) -> int:
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dev in devices]
    return int(max(peaks))


def read_per_layer(bench: dict, name: str, ctx: dict, root: str) -> dict:
    """Each per-layer metric of the cell, by its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, name):
            continue
        path = os.path.join(root, "bench", "metrics", m["name"] + ".py")
        value = _load_module(path, "bench_metric_" + m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(argv=None, *, root: str = ROOT, src: str | None = None,
        require_chip: bool = True, cache: bool = True, patch=None,
        t0: float = T0) -> dict:
    """One run; returns the result object (the last line of stdout).
    `patch(driver)`, for tests, changes the timed path before set-up."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    found = find_cell(bench, args.workload, root)
    cell, cfg, mix = found["cell"], found["cfg"], found["mix"]
    src = src or os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError("the program (src/repro) is not in this checkout")
    if src not in sys.path:
        sys.path.insert(0, src)

    import jax
    from bench.peaks import PEAKS
    from repro.launch import configure_compile_cache

    if cache:
        configure_compile_cache()
        # Cache every program, however fast it compiles: a later run of
        # the cell then compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_s = {}

    def listen(event, duration, **_):
        if event in COMPILE_EVENTS:
            compile_s[event] = compile_s.get(event, 0.0) + duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    phases = {"import_s": time.perf_counter() - t0}
    chips = int(cell["chips"])
    device = device_info(jax.devices(), chips, require_chip)
    devices = jax.devices()[:chips]
    phases["devices_s"] = time.perf_counter() - t0 - sum(phases.values())

    with matmul_precision(cfg):
        driver_mod = _load_module(
            os.path.join(root, "bench", "drivers", mix["driver"] + ".py"),
            "bench_driver_" + mix["driver"])
        drv = driver_mod.Driver(cfg, mix, devices)
        if patch is not None:
            patch(drv)
        seconds = min(args.seconds, mix["trace_seconds"]) if args.trace \
            else args.seconds
        phases["driver_s"] = time.perf_counter() - t0 - sum(phases.values())
        drv.load(args.seed, seconds)
        phases.update(getattr(drv, "phases", {}))
        phases["load_s"] = time.perf_counter() - t0 - sum(phases.values())
        drv.warm()
        setup_s = time.perf_counter() - t0
        phases["warm_s"] = setup_s - sum(phases.values())
        compile_total = sum(compile_s.values())

        reduced = None
        if args.trace:
            from bench import trace
            with tempfile.TemporaryDirectory() as tdir:
                with trace.capture(tdir):
                    with jax.profiler.TraceAnnotation(trace.WINDOW):
                        win = drv.window(seconds, jax.profiler.TraceAnnotation)
                loaded = trace.load(tdir)
            reduced = trace.reduce(loaded, drv.classes())
        else:
            win = drv.window(seconds, lambda name: contextlib.nullcontext())
        device["memory_peak_bytes"] = memory_peak(devices)
        e2e = drv.end_to_end(win)
        attempted, failed = drv.counts(win)
        drv.release()
        checks = drv.check(win)
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "pool_refills": win.get("refills", 0)}
    if args.trace:
        ctx = {"cell": cell, "cfg": cfg, "mix": mix, "trace": reduced,
               "end_to_end": e2e, "work": drv.work(), "chips": chips,
               "peaks": PEAKS.get(device["kind"]), "compile_s": compile_total,
               "window": {k: v for k, v in win.items()
                          if isinstance(v, (int, float))}}
        result["metrics"] = read_per_layer(bench, cell["name"], ctx, root)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        values = dict(e2e, setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if applies(m, cell["name"])}
    result["device"] = device
    result["setup_phases"] = phases
    if reduced is not None:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None, **run_kw) -> int:
    """Print the result's last line; `run_kw` go to `run` (tests)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        result = run(argv, **run_kw)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if result.pop("pool_refills", 0):
        print("note: the id pool ran out and was refilled inside the window",
              file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
