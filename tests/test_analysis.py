"""HLO static analyzer: loop multiplicity, flops, collective bytes."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.topology import make_mesh
from repro.launch.analysis import analyze_hlo


def test_loop_free_matches_cost_analysis():
    def mm(x, w):
        return jnp.dot(x, w)
    c = jax.jit(mm).lower(
        jax.ShapeDtypeStruct((256, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 64), jnp.float32)).compile()
    st = analyze_hlo(c.as_text())
    ca = c.cost_analysis()
    assert st.flops == float(ca["flops"]) == 2 * 256 * 128 * 64


def test_scan_flops_multiplied():
    def h(x):
        def body(c, _):
            return c @ x, None
        out, _ = jax.lax.scan(body, jnp.eye(64), None, length=10)
        return out
    c = jax.jit(h).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    st = analyze_hlo(c.as_text())
    assert st.flops == 10 * 2 * 64 ** 3
    assert st.loops >= 1


def test_nested_scan_collectives():
    mesh = make_mesh((8,), ("x",))

    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return jax.lax.ppermute(
                    c2, "x", [(i, (i + 1) % 8) for i in range(8)]), None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        c, _ = jax.lax.scan(outer, x, None, length=4)
        return c

    t = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                              out_specs=P("x"))).lower(
        jax.ShapeDtypeStruct((8, 4), jnp.float32)).compile().as_text()
    st = analyze_hlo(t)
    assert st.coll_ops == 12
    assert st.coll_wire_bytes == 12 * 16  # f32[1,4] per hop


def test_allreduce_wire_model():
    mesh = make_mesh((8,), ("x",))

    def f(x):
        return jax.lax.psum(x, "x")

    t = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                              out_specs=P("x"))).lower(
        jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile().as_text()
    st = analyze_hlo(t)
    # ring model: 2 * bytes * (n-1)/n
    assert abs(st.coll_wire_bytes - 2 * 128 * 4 * 7 / 8) < 1e-6
