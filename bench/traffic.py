"""The one traffic generator: makes a mix's requests (its parameters
are a file under `bench/traffic/`) from the seed.

Request ids are made on the device in one jitted call per chunk and
copied to the host before the window, so generating them costs the
window nothing; the window then sends them from host memory, as a
front end would. The same seed gives the same ids.
"""
from __future__ import annotations

import math

import numpy as np

from bench import weights


def _radix(v: int) -> tuple[int, int]:
    """V = A * B with A the largest divisor of V not above sqrt(V)."""
    a = int(math.isqrt(v))
    while v % a:
        a -= 1
    return a, v // a


def permute(key, x, v: int, xp, rounds: int = 4):
    """A seeded bijection of [0, v): a Feistel network over the mixed
    radix v = A * B (format-preserving; uint32 arithmetic only)."""
    a, b = _radix(v)
    for r in range(rounds):
        hi, lo = x // xp.uint32(b), x % xp.uint32(b)
        f = weights.hash_index(key + xp.uint32(r * 0x9E3779B9 & 0xFFFFFFFF),
                               (lo,), xp) % xp.uint32(a)
        x = lo * xp.uint32(a) + (hi + f) % xp.uint32(a)
        a, b = b, a
    return x


def id_sampler(rows: int, tables: int, dist: str, alpha: float = 0.0):
    """jit: (key uint32[2+T], first batch, n batches, batch) -> int32
    ids (n, batch * tables) in [0, rows), each row a batch (batch,
    tables) in row-major order. Zipf draws a rank by the continuous
    inverse CDF of a bounded power law, P(k) ~ k^-alpha on
    [1, rows], then maps ranks to rows by a seeded per-table permutation,
    so each table has its own hot rows."""
    import jax
    import jax.numpy as jnp

    def sample(keys, first, n, batch):
        shape = (n, batch, tables)
        b = jax.lax.broadcasted_iota(jnp.uint32, shape, 0) + first
        q = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        t = jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
        h = weights.hash_index(keys[0], (b, q, t), jnp)
        u = (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
        if dist == "uniform":
            k = jnp.floor(u * rows)
        elif dist == "zipf":
            c = jnp.float32((rows + 1) ** (1 - alpha) - 1)
            k = jnp.floor((1 + u * c) ** jnp.float32(1 / (1 - alpha))) - 1
        else:
            raise ValueError(f"unknown id distribution {dist!r}")
        k = jnp.clip(k, 0, rows - 1).astype(jnp.uint32)
        if dist == "zipf":
            k = permute(keys[1:][t], k, rows, jnp)
        return k.astype(jnp.int32).reshape(n, batch * tables)

    return jax.jit(sample, static_argnums=(2, 3))


def id_keys(seed: int, tables: int) -> np.ndarray:
    """The id sampler's keys: one for the draws, one per table's
    permutation."""
    return np.array([weights.key_of(seed, 1000)]
                    + [weights.key_of(seed, 2000 + t) for t in range(tables)],
                    np.uint32)


class IdPool:
    """Every batch of a window, made before it: `n` batches of distinct
    draws, copied to host memory in equal chunks and kept there as they
    came. `pool[i]` is batch i; `take(i)` past the pool makes the next
    chunk, inside the window, and counts it."""

    def __init__(self, sampler, keys, n: int, batch: int, tables: int,
                 chunk_bytes: int = 256 << 20):
        self.sampler, self.keys = sampler, keys
        self.batch, self.tables = batch, tables
        most = max(1, chunk_bytes // (batch * tables * 4))
        self.chunk = math.ceil(n / math.ceil(n / most))
        self.refills = 0
        self.chunks = []
        while len(self) < n:
            self._add()

    def _add(self) -> None:
        flat = np.asarray(self.sampler(self.keys, np.uint32(len(self)),
                                       self.chunk, self.batch))
        self.chunks.append(flat.reshape(self.chunk, self.batch, self.tables))

    def __len__(self) -> int:
        return len(self.chunks) * self.chunk

    def __getitem__(self, i: int) -> np.ndarray:
        return self.chunks[i // self.chunk][i % self.chunk]

    def take(self, i: int) -> np.ndarray:
        if i >= len(self):
            self.refills += 1
            self._add()
        return self[i]
