"""Counter-based random numbers the benchmark makes its data from.

Every value is a pure function of (seed, stream, index): a 32-bit integer
hash of the index, turned into a float exactly. The device makes whole
arrays with it in one jitted call; the reference recomputes on the host,
with numpy, just the entries it needs (the looked-up rows of a table that
no host could hold), and gets the same bits. Nothing here imports the
program under test.
"""
from __future__ import annotations

import numpy as np

# lowbias32 (C. Wellons, "Hash function prospector"): two multiplies,
# three xor-shifts, a bijection on uint32.
_M1, _M2 = 0x7FEB352D, 0x846CA68B
# One odd constant per array axis, so (i, j) and (j, i) hash apart.
_AXIS = (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35)


def _mix(x, xp):
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(_M1)
    x = x ^ (x >> xp.uint32(15))
    x = x * xp.uint32(_M2)
    return x ^ (x >> xp.uint32(16))


def key_of(seed: int, stream: int) -> np.uint32:
    """The 32-bit key of one stream under a seed of up to 64 bits."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    with np.errstate(over="ignore"):
        k = _mix(np.uint32(hi) ^ np.uint32(stream * 0x27D4EB2F & 0xFFFFFFFF),
                 np)
        return _mix(np.uint32(lo) ^ k, np)


def hash_index(key, idx, xp):
    """uint32 hash of a multi-index (a tuple of uint32 arrays)."""
    h = key
    for a, i in enumerate(idx):
        h = _mix(h ^ (i * xp.uint32(_AXIS[a])), xp)
    return h


def to_uniform(h, scale: float, xp):
    """uint32 -> float32 in [-scale, scale), exactly the same on any
    backend: 24 bits, a power-of-two step, and `scale` a power of two."""
    u = (h >> xp.uint32(8)).astype(xp.float32) * xp.float32(2.0 ** -23)
    return (u - xp.float32(1.0)) * xp.float32(scale)


def pow2_scale(x: float) -> float:
    """The power of two nearest to `x` (keeps `to_uniform` exact)."""
    return float(2.0 ** round(np.log2(x)))


def uniform_np(key, shape, scale: float) -> np.ndarray:
    """A whole array of uniform values, on the host (numpy)."""
    idx = np.meshgrid(*[np.arange(n, dtype=np.uint32) for n in shape],
                      indexing="ij")
    with np.errstate(over="ignore"):
        return to_uniform(hash_index(np.uint32(key), idx, np), scale, np)


def uniform_rows_np(key, rows: np.ndarray, dim: int, scale: float,
                    lead: int | None = None) -> np.ndarray:
    """Rows `rows` (any int array) of a (lead, V, dim) or (V, dim) array
    made like `uniform_np`: result shape rows.shape + (dim,). With `lead`,
    `rows` has that many entries on its last axis, one per table."""
    r = rows.astype(np.uint32)[..., None]
    d = np.arange(dim, dtype=np.uint32)
    with np.errstate(over="ignore"):
        if lead is None:
            idx = (r, d)
        else:
            t = np.arange(lead, dtype=np.uint32)
            t = t.reshape((1,) * (rows.ndim - 1) + (lead, 1))
            idx = (t, r, d)
        return to_uniform(hash_index(np.uint32(key), idx, np), scale, np)
