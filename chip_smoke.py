"""Chip smoke test: the system's main path, once, on TPU, against references.

  python chip_smoke.py             one TPU v5e chip
  python chip_smoke.py --chips 4   the four-chip path only (a 2x2 v5e host)

One chip: each Pallas kernel runs compiled at the widths the main path uses
and is checked against `kernels/ref.py`; then DLRM inference at the paper's
Table 2 widths (100 tables x 32 dims, FC 2048/512/256; 1,000,000 rows per
table, one chip's share of the four-chip deployment) serves 8 request
batches of 256 queries through CollectiveEngine + shard_map, once with the
jnp lookup and once with the Pallas gather, each batch checked against
`dlrm_reference`.

Four chips: DLRM at the published 4,000,000 rows per table, sharded over a
4-way 'model' axis, with and without the collective matmul and on
backend="native", checked against the FC stack run on one device over the
looked-up rows (the full tables fit on no single chip); then every engine
collective at 4 KiB, 128 KiB and 4 MiB (the paper's Fig. 10 grid) against
its numpy oracle and backend="native", one int8-compressed allreduce with
the Pallas codec, and one issue/drain through the Sequencer.

Only a TPU that `HwSpec` describes is accepted: anything else exits
non-zero before any phase. The times printed are set-up and smoke timings,
not benchmark results. The last line of stdout is one JSON object,
{"ok": true, "device": {...}}; a failed phase exits non-zero before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import ParallelConfig  # noqa: E402
from repro.configs.dlrm import CONFIG as DLRM_TABLE2  # noqa: E402
from repro.core import CollectiveEngine  # noqa: E402
from repro.core.hw_spec import hw_for_devices  # noqa: E402
from repro.core.topology import make_mesh  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import configure_compile_cache  # noqa: E402
from repro.models import dlrm as dlrm_mod  # noqa: E402
from repro.parallel.ops import ParCtx  # noqa: E402

ONE_CHIP_ROWS = 1_000_000       # one chip's share of the 4,000,000 rows
BATCHES, BATCH_SIZE = 8, 256
DLRM_TOL = dict(atol=1e-2, rtol=1e-2)   # examples/dlrm_serve.py's
COLLECTIVE_BYTES = (4 << 10, 128 << 10, 4 << 20)
AX, ROOT = "x", 1


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
    log(f"device: jax {jax.__version__} platform={info['platform']} "
        f"kind={info['kind']!r} count={info['count']}")
    return info


def require_chip(info: dict, chips: int) -> None:
    """Exit non-zero unless JAX runs on `chips` TPUs HwSpec describes."""
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{info['platform']!r}); this script runs only on a chip")
    try:
        hw_for_devices(jax.devices())
    except ValueError as e:
        sys.exit(f"chip_smoke: {e}")
    if info["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX sees "
                 f"{info['count']} device(s)")


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# Kernels
# --------------------------------------------------------------------------

def phase_kernels(*, interpret: bool, seed: int = 0, n_elems: int = 1 << 20,
                  mm_shape=(256, 3200, 2048), tables: int = 100,
                  rows: int = 8192, dim: int = 32,
                  batch: int = BATCH_SIZE) -> None:
    """Every Pallas kernel once, against its `kernels/ref.py` oracle:
    the codec and combine at a 4 MiB wire segment, the matmul at DLRM
    FC1 for one batch, the gather at the DLRM lookup widths."""
    rng = np.random.default_rng(seed)

    x = jnp.asarray(rng.normal(size=(n_elems,)) * 13, jnp.float32)
    (q, s), t = _timed(lambda v: ops.quantize_int8(v, interpret=interpret), x)
    q_ref, s_ref = ref.quantize_blocks(x.reshape(-1, ref.QUANT_BLOCK))
    np.testing.assert_allclose(np.asarray(s)[:s_ref.shape[0]],
                               np.asarray(s_ref), rtol=1e-6)
    # codes may differ by one step where x/scale rounds at exactly .5
    code_diff = np.abs(np.asarray(q, np.int32)[:n_elems]
                       - np.asarray(q_ref, np.int32).reshape(-1))
    assert code_diff.max() <= 1, code_diff.max()
    back = ops.dequantize_int8(q, s, interpret=interpret)[:n_elems]
    np.testing.assert_allclose(
        np.asarray(back),
        np.asarray(ref.dequantize_blocks(q.reshape(-1, ref.QUANT_BLOCK),
                                         s)).reshape(-1)[:n_elems],
        rtol=1e-6)
    log(f"kernel quantize/dequantize int8 ({n_elems} f32): ok "
        f"[smoke timing {t * 1e3:.1f} ms incl. compile]")

    for dtype in (jnp.float32, jnp.bfloat16):
        a = jnp.asarray(rng.normal(size=(n_elems,)), dtype)
        b = jnp.asarray(rng.normal(size=(n_elems,)), dtype)
        out = ops.fused_combine(a, b, "add", interpret=interpret)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32),
            np.asarray(ref.fused_combine(a, b, "add"), np.float32))
        log(f"kernel fused_combine add {jnp.dtype(dtype).name} "
            f"({n_elems}): ok")

    m, k, n = mm_shape
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) / np.sqrt(k), jnp.float32)
    out = np.asarray(ops.matmul(a, w, interpret=interpret))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.matmul(a, w))
    err = np.abs(out - want).max() / np.abs(want).max()
    assert err < 1e-2, err
    log(f"kernel matmul {m}x{k}x{n} f32: ok (max err {err:.2e} of max)")

    tab = jnp.asarray(rng.normal(size=(tables, rows, dim)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, rows, (tables, batch)), jnp.int32)
    got = ops.embedding_gather(tab, idx, interpret=interpret)
    want = jax.vmap(ref.gather_rows)(tab, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    log(f"kernel embedding_gather {tables}x{rows}x{dim}, {batch} ids/table: "
        f"ok")


# --------------------------------------------------------------------------
# DLRM inference
# --------------------------------------------------------------------------

def dlrm_server(cfg, mesh, *, backend: str = "microcode",
                use_pallas: bool = False, collective_matmul: bool = False):
    """The jitted shard_map serving step of examples/dlrm_serve.py."""
    pcfg = ParallelConfig(backend=backend,
                          collective_matmul=collective_matmul)
    ctx = ParCtx(engine=CollectiveEngine(mesh, backend=backend), pcfg=pcfg,
                 mesh=mesh)
    specs = dlrm_mod.dlrm_specs(cfg, mesh.shape["model"])
    return jax.jit(jax.shard_map(
        lambda p, i: dlrm_mod.dlrm_forward(p, i, ctx, use_pallas),
        mesh=mesh, in_specs=(specs, P(None, None)),
        out_specs=P(None, None), check_vma=False))


def _requests(cfg, batches: int, batch_size: int, seed: int):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, cfg.rows_per_table,
                                     (batch_size, cfg.n_tables)), jnp.int32)
            for _ in range(batches)]


def _serve_and_check(name, serve, params, reqs, reference) -> None:
    """Serve every request batch; each must match `reference(batch)`."""
    times = []
    for i, r in enumerate(reqs):
        out, t = _timed(serve, params, r)
        times.append(t)
        out = np.asarray(out)
        assert out.shape == (r.shape[0], 1), out.shape
        assert np.isfinite(out).all(), f"{name}: batch {i} not finite"
        np.testing.assert_allclose(out, reference(r), **DLRM_TOL,
                                   err_msg=f"{name}: batch {i}")
    steady = ", ".join(f"{t * 1e3:.2f}" for t in times[1:])
    log(f"dlrm {name}: {len(reqs)} batches of {reqs[0].shape[0]} match the "
        f"reference; set-up (compile + batch 0) {times[0]:.2f} s; "
        f"smoke timing per batch [{steady}] ms")


def phase_dlrm(cfg, mesh, *, seed: int = 0, batches: int = BATCHES,
               batch_size: int = BATCH_SIZE) -> None:
    """One device: jnp and Pallas lookups against `dlrm_reference`."""
    params, t = _timed(dlrm_mod.dlrm_init, cfg, mesh, seed)
    log(f"dlrm params: {cfg.n_tables} tables x "
        f"{params['tables'].shape[1]} rows x {cfg.emb_dim} "
        f"({params['tables'].nbytes / 1e9:.2f} GB) initialised on device "
        f"in {t:.2f} s (set-up)")
    reqs = _requests(cfg, batches, batch_size, seed)
    reference = jax.jit(dlrm_mod.dlrm_reference)
    for use_pallas in (False, True):
        serve = dlrm_server(cfg, mesh, use_pallas=use_pallas)
        _serve_and_check("pallas lookup" if use_pallas else "jnp lookup",
                         serve, params, reqs,
                         lambda r: np.asarray(reference(params, r)))


def looked_up_reference(params, mesh):
    """Oracle for tables no single device holds: XLA's own partitioned
    gather of the looked-up rows, then the FC stack on one device."""
    tables = params["tables"]
    gather = jax.jit(
        lambda t, i: t[jnp.arange(t.shape[0])[None, :], i],
        out_shardings=NamedSharding(mesh, P()))
    dev0 = mesh.devices.flat[0]
    fc = jax.device_put(params["fc"], dev0)
    mlp = jax.jit(dlrm_mod.dlrm_mlp_reference)

    def reference(idx):
        rows = jax.device_put(gather(tables, idx), dev0)
        return np.asarray(mlp(fc, rows.reshape(idx.shape[0], -1)))
    return reference


def phase_dlrm_sharded(cfg, mesh, *, seed: int = 0, batches: int = BATCHES,
                       batch_size: int = BATCH_SIZE) -> None:
    """Tables sharded over 'model': the microcode engine with and without
    the collective matmul, and backend="native", on the same params."""
    params, t = _timed(dlrm_mod.dlrm_init, cfg, mesh, seed)
    tp = mesh.shape["model"]
    log(f"dlrm params: {cfg.n_tables} tables x {params['tables'].shape[1]} "
        f"rows x {cfg.emb_dim} ({params['tables'].nbytes / 1e9:.2f} GB, "
        f"{params['tables'].nbytes / tp / 1e9:.2f} GB per device) "
        f"initialised on device in {t:.2f} s (set-up)")
    reqs = _requests(cfg, batches, batch_size, seed)
    reference = looked_up_reference(params, mesh)
    outs = {}
    for name, kw in (("microcode", {}),
                     ("microcode collective_matmul",
                      {"collective_matmul": True}),
                     ("native", {"backend": "native"})):
        serve = dlrm_server(cfg, mesh, **kw)
        _serve_and_check(name, serve, params, reqs, reference)
        outs[name] = np.asarray(serve(params, reqs[0]))
    for name in ("microcode", "microcode collective_matmul"):
        np.testing.assert_allclose(outs[name], outs["native"], **DLRM_TOL)
    log("dlrm microcode == native on batch 0")


# --------------------------------------------------------------------------
# Collectives
# --------------------------------------------------------------------------

def _collectives(eng, x):
    return {
        "allreduce": eng.allreduce(x, AX),
        "reduce_scatter": eng.reduce_scatter(x, AX),
        "allgather": eng.allgather(x, AX),
        "bcast": eng.bcast(x, AX, root=ROOT),
        "reduce": eng.reduce(x, AX, root=ROOT),
        "gather": eng.gather(x, AX, root=ROOT),
        "alltoall": eng.alltoall(x, AX),
    }


def collective_oracle(name: str, xs: np.ndarray):
    """Rank -> expected result (None where MPI leaves it undefined)."""
    n, m = xs.shape
    c = m // n
    total = xs.sum(0)
    return [{
        "allreduce": total,
        "reduce_scatter": total[r * c:(r + 1) * c],
        "allgather": xs.reshape(-1),
        "bcast": xs[ROOT],
        "reduce": total if r == ROOT else None,
        "gather": xs.reshape(-1) if r == ROOT else None,
        "alltoall": xs[:, r * c:(r + 1) * c].reshape(-1),
    }[name] for r in range(n)]


def _run_all(eng, xs):
    mapped = eng.run(
        lambda v: {k: o[None] for k, o in _collectives(eng, v[0]).items()},
        in_specs=P(AX), out_specs=P(AX))
    return {k: np.asarray(v) for k, v in mapped(jnp.asarray(xs)).items()}


def _check_ranks(label, got, want) -> None:
    for r, w in enumerate(want):
        if w is not None:
            np.testing.assert_array_equal(got[r], w, err_msg=f"{label} "
                                          f"rank {r}")


def phase_collectives(mesh, *, seed: int = 0,
                      sizes=COLLECTIVE_BYTES) -> None:
    """Every engine collective, microcode vs numpy oracle vs native, on
    integer-valued fp32 (sums are exact, so all three must agree bitwise);
    then the Pallas int8 codec and one Sequencer issue/drain."""
    n = mesh.shape[AX]
    rng = np.random.default_rng(seed)
    micro = CollectiveEngine(mesh, backend="microcode")
    native = CollectiveEngine(mesh, backend="native")
    for nbytes in sizes:
        m = nbytes // 4
        xs = rng.integers(-40, 40, (n, m)).astype(np.float32)
        (got_m, t_m), (got_n, t_n) = (
            _timed(_run_all, micro, xs), _timed(_run_all, native, xs))
        for name in got_m:
            want = collective_oracle(name, xs)
            _check_ranks(f"microcode {name} {nbytes}B", got_m[name], want)
            _check_ranks(f"native {name} {nbytes}B", got_n[name], want)
        log(f"collectives {nbytes} B/rank x {n} ranks: "
            f"{', '.join(got_m)} == oracle == native; set-up (compile + "
            f"run) microcode {t_m:.2f} s, native {t_n:.2f} s")

    m = max(sizes) // 4
    xs = rng.normal(size=(n, m)).astype(np.float32)
    want = xs.sum(0)
    for use_pallas in (False, True):
        eng = CollectiveEngine(mesh, backend="microcode",
                               use_pallas=use_pallas)
        out = np.asarray(eng.run(
            lambda v, e=eng: e.allreduce(v[0], AX, algorithm="ring",
                                         compression="int8")[None],
            in_specs=P(AX), out_specs=P(AX))(jnp.asarray(xs)))
        rel = np.abs(out - want).max() / np.abs(want).max()
        assert rel < 0.02, (use_pallas, rel)
        log(f"int8 allreduce {max(sizes)} B/rank use_pallas={use_pallas}: "
            f"max err {rel:.2e} of max")

    xs = rng.integers(-40, 40, (n, min(sizes) // 4)).astype(np.float32)
    eng = CollectiveEngine(mesh, backend="microcode")

    def queued(v):
        r1 = eng.iallreduce(v[0], AX)
        r2 = eng.ibcast(v[0], AX, root=ROOT)
        eng.queue.drain(AX)
        return r1.result[None], r2.result[None]

    a, b = eng.run(queued, in_specs=P(AX), out_specs=(P(AX), P(AX)))(
        jnp.asarray(xs))
    _check_ranks("sequencer allreduce", np.asarray(a),
                 collective_oracle("allreduce", xs))
    _check_ranks("sequencer bcast", np.asarray(b),
                 collective_oracle("bcast", xs))
    log("sequencer issue/drain (allreduce + bcast) == oracle")


# --------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = device_info()
    require_chip(info, args.chips)
    log(f"compile cache: {configure_compile_cache()}")
    if args.chips == 1:
        assert not ops._interpret(), "Pallas kernels would be interpreted"
        phase_kernels(interpret=False, seed=args.seed)
        cfg = dataclasses.replace(DLRM_TABLE2, rows_per_table=ONE_CHIP_ROWS)
        log(f"dlrm config: Table 2 widths, rows_per_table "
            f"{DLRM_TABLE2.rows_per_table} cut to {ONE_CHIP_ROWS} "
            f"(one chip's share of 4)")
        phase_dlrm(cfg, make_mesh((1, 1, 1), ("pod", "data", "model")),
                   seed=args.seed)
    else:
        phase_dlrm_sharded(
            DLRM_TABLE2, make_mesh((1, 1, 4), ("pod", "data", "model")),
            seed=args.seed)
        phase_collectives(make_mesh((4,), (AX,)), seed=args.seed)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device 0 peak_bytes_in_use {stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["kind"],
        "count": info["count"]}}), flush=True)


if __name__ == "__main__":
    main()
