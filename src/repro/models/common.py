"""Shared model machinery: param builder, norms, rope, activations.

The `Builder` gives every layer a single definition that can produce
  mode='init'   real initialized jnp arrays (smoke tests, examples;
                `sharded_initializer` runs it under jit, into shards),
  mode='spec'   a PartitionSpec pytree (shard_map in_specs, checkpointing),
  mode='shape'  ShapeDtypeStructs with NamedSharding (the dry-run: no
                allocation ever happens for the 26B configs).

Spec conventions over the production mesh (pod, data, model):
  * 'data'  appearing in a param spec = FSDP shard (gathered at use),
  * 'model' = tensor-parallel shard,
  * axes absent from a spec mean the param is replicated there and its
    gradient must be summed over that axis (runtime/grad_sync handles it).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


@dataclasses.dataclass
class Builder:
    """One param definition -> init array | spec | ShapeDtypeStruct."""

    mode: str                      # 'init' | 'spec' | 'shape'
    key: Optional[jax.Array] = None
    mesh: Optional[jax.sharding.Mesh] = None
    dtype: object = jnp.float32
    counter: int = 0

    def _next_key(self):
        self.counter += 1
        return jax.random.fold_in(self.key, self.counter)

    def param(self, shape, spec: P, init: str = "normal",
              scale: Optional[float] = None, dtype=None):
        dtype = dtype or self.dtype
        if self.mode == "spec":
            return spec
        if self.mode == "shape":
            if self.mesh is not None:
                return jax.ShapeDtypeStruct(
                    shape, dtype, sharding=NamedSharding(self.mesh, spec))
            return jax.ShapeDtypeStruct(shape, dtype)
        k = self._next_key()
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "normal":
            if scale is None:
                scale = 1.0 / math.sqrt(shape[0] if len(shape) > 1 else 1.0)
            return (jax.random.normal(k, shape, jnp.float32) * scale
                    ).astype(dtype)
        if init == "ssm_a":  # mamba A_log in [log 1, log 16]
            u = jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
            return jnp.log(u).astype(jnp.float32)
        if init == "ssm_dt":  # dt bias ~ softplus^-1(U(1e-3, 1e-1))
            u = jax.random.uniform(k, shape, jnp.float32, 1e-3, 1e-1)
            return jnp.log(jnp.expm1(u)).astype(jnp.float32)
        raise ValueError(init)


def sharded_initializer(build, mesh, specs, dtype=jnp.float32):
    """jit of key -> `build(Builder("init"))`, each leaf created in place
    with the NamedSharding of its spec: every device initialises only its
    own shard, so no whole parameter (or RNG temporary) lands on one
    device."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(lambda key: build(Builder("init", key=key, dtype=dtype)),
                   out_shardings=shardings)


# --------------------------------------------------------------------------
# Numerics
# --------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-6, psum_axis=None, engine=None):
    """RMSNorm; if the feature dim is TP-sharded, pass psum_axis to reduce
    the mean-square across the shard group (engine optional for microcode)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if psum_axis is not None:
        if engine is not None and engine.backend == "microcode":
            ms = engine.allreduce(ms, psum_axis) / engine.mesh.shape[psum_axis]
        else:
            ms = jax.lax.pmean(ms, psum_axis)
    y = xf * jax.lax.rsqrt(ms + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embeddings. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x)


def sinusoidal_positions(seq_len: int, d_model: int, offset=0):
    """Whisper-style absolute sinusoidal embeddings, computed on the fly."""
    pos = jnp.arange(seq_len, dtype=jnp.float32) + offset
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
