"""Work counts, from the configuration's shapes alone.

Never from compiled HLO: a count read off the implementation moves when
the implementation changes, and a roofline's denominator must not.
"""
from __future__ import annotations


def fc_dims(cfg: dict) -> list[int]:
    """Layer widths of the FC stack, input first: 3200, 2048, 512, 256, 1
    for the paper's Table 2."""
    return ([cfg["n_tables"] * cfg["emb_dim"]] + list(cfg["fc_dims"])
            + [cfg["out_dim"]])


def dlrm_fc_params(cfg: dict) -> int:
    """Weights of the FC stack (biases excluded): 7,733,504 for Table 2."""
    d = fc_dims(cfg)
    return sum(a * b for a, b in zip(d[:-1], d[1:]))


def dlrm_flops_per_query(cfg: dict) -> int:
    """Two FLOP (multiply, add) per FC weight per query: 15,467,008."""
    return 2 * dlrm_fc_params(cfg)


def dlrm_lookup_bytes(cfg: dict, batch: int) -> int:
    """Bytes the lookup has to move for one batch: every looked-up row
    read (float32), the concatenated vector written, the int32 ids read.
    B=256 at Table 2 widths: 6,656,000 B."""
    t, d = cfg["n_tables"], cfg["emb_dim"]
    return batch * (t * d * 4 + t * d * 4 + t * 4)


def bus_bytes(collective: str, msg_bytes: int, n: int) -> float:
    """Bus bytes of one call, nccl-tests' definition (PERFORMANCE.md of
    NVIDIA/nccl-tests): the size nccl-tests reports times its bus factor.
    `msg_bytes` is the per-rank input. nccl-tests' size is that input for
    allreduce, reduce_scatter, alltoall, bcast and reduce, and the gathered
    output (n inputs) for allgather; gather counts the bytes the busiest
    rank (the root) has to receive, (n-1) inputs, like allgather."""
    if collective == "allreduce":
        return msg_bytes * 2 * (n - 1) / n
    if collective in ("reduce_scatter", "alltoall"):
        return msg_bytes * (n - 1) / n
    if collective in ("allgather", "gather"):
        return msg_bytes * n * (n - 1) / n
    if collective in ("bcast", "reduce"):
        return float(msg_bytes)
    raise ValueError(f"no bus factor for {collective!r}")
