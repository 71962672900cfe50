"""Trip-count-corrected HLO cost extraction (the roofline's data source)."""
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo_cost import corrected_cost
from repro.core.fabric import Fabric


def _cc(f, *args, axis_sizes=None):
    text = jax.jit(f).lower(*args).compile().as_text()
    return corrected_cost(text, axis_sizes or {"data": 1, "model": 1})


def test_scan_flops_multiplied_by_trip_count():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cc = _cc(f, x, x)
    assert abs(cc.flops / (2 * 128 ** 3 * 10) - 1) < 0.01


def test_nested_scan_flops():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return jnp.tanh(ci @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cc = _cc(f, x, x)
    assert abs(cc.flops / (2 * 128 ** 3 * 15) - 1) < 0.01


def test_xla_cost_analysis_undercounts_scans():
    """The reason hlo_cost exists: XLA counts while bodies once."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cost = jax.jit(f).lower(x, x).compile().cost_analysis()
    if isinstance(cost, list):          # older jax: one entry per program
        cost = cost[0]
    assert cost["flops"] < 2 * 128 ** 3 * 2       # ~1x, not 10x


def test_collective_bytes_in_scan(mesh8):
    fab = Fabric(("data",), (4,), "photonic")

    def g(ws):
        def body(c, w_shard):
            w = fab.all_gather(w_shard)
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, jnp.ones((128, 128)), ws)
        return jnp.sum(y)

    gm = jax.shard_map(g, mesh=mesh8, in_specs=P(None, "data", None),
                       out_specs=P(), axis_names={"data"}, check_vma=False)
    ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32,
                              sharding=NamedSharding(mesh8,
                                                     P(None, "data", None)))
    with jax.set_mesh(mesh8):
        text = jax.jit(gm).lower(ws).compile().as_text()
    cc = corrected_cost(text, {"data": 4, "model": 2})
    # 6 layers x 3 ring steps x 32x128 f32 shard
    assert cc.collective_bytes["data"]["_bytes"] == 6 * 3 * 32 * 128 * 4


def test_axis_classification(mesh8):
    def f(x):
        a = jax.lax.psum(x, "data")
        b = jax.lax.psum(x, "model")
        return a + b
    fm = jax.shard_map(f, mesh=mesh8, in_specs=P("data", "model"),
                       out_specs=P("data", "model"), axis_names={"data",
                                                                 "model"})
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32,
                             sharding=NamedSharding(mesh8, P("data",
                                                             "model")))
    with jax.set_mesh(mesh8):
        text = jax.jit(fm).lower(x).compile().as_text()
    cc = corrected_cost(text, {"data": 4, "model": 2})
    assert cc.collective_bytes.get("model", {}).get("_bytes", 0) > 0
    assert cc.collective_bytes.get("data", {}).get("_bytes", 0) > 0
