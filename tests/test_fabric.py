"""Photonic ring collectives vs XLA natives, and AD-transpose identities."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.fabric import Fabric


def smap(mesh, f, in_specs, out_specs, axes={"data"}):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, axis_names=axes,
                                 check_vma=False))


@pytest.fixture(scope="module")
def fabs(mesh_data8):
    return (Fabric(("data",), (8,), "photonic"),
            Fabric(("data",), (8,), "eps"), mesh_data8)


def test_all_gather_matches_native(fabs):
    fab, eps, mesh = fabs
    x = jnp.arange(32.).reshape(32, 1) + 1
    ag_p = smap(mesh, fab.all_gather, P("data", None), P(None, None))(x)
    ag_e = smap(mesh, eps.all_gather, P("data", None), P(None, None))(x)
    np.testing.assert_array_equal(ag_p[:32], x)
    np.testing.assert_array_equal(ag_p, ag_e)


def test_all_gather_axis1(fabs):
    fab, eps, mesh = fabs
    x = jnp.arange(64.).reshape(4, 16)
    f = lambda s: fab.all_gather(s, axis=1)
    g = lambda s: eps.all_gather(s, axis=1)
    np.testing.assert_array_equal(
        smap(mesh, f, P(None, "data"), P(None, None))(x),
        smap(mesh, g, P(None, "data"), P(None, None))(x))


def test_reduce_scatter_matches_native(fabs):
    fab, eps, mesh = fabs
    x = jnp.arange(32.).reshape(32, 1)
    rs_p = smap(mesh, fab.reduce_scatter, P(None, None), P("data", None))(x)
    rs_e = smap(mesh, eps.reduce_scatter, P(None, None), P("data", None))(x)
    np.testing.assert_allclose(rs_p, rs_e)
    np.testing.assert_allclose(rs_p[:4, 0], 8 * x[:4, 0])


def test_all_reduce_matches_native(fabs):
    fab, eps, mesh = fabs
    x = jnp.arange(33.).reshape(33, 1)  # odd size exercises padding
    ar_p = smap(mesh, fab.all_reduce, P(None, None), P(None, None))(x)
    np.testing.assert_allclose(ar_p, 8 * x)


def test_all_to_all_matches_native(fabs):
    fab, eps, mesh = fabs
    y = jnp.arange(64.).reshape(64, 1)
    f = lambda s: fab.all_to_all(s.reshape(8, 1, 1)).reshape(8, 1)
    g = lambda s: eps.all_to_all(s.reshape(8, 1, 1)).reshape(8, 1)
    np.testing.assert_allclose(
        smap(mesh, f, P("data", None), P("data", None))(y),
        smap(mesh, g, P("data", None), P("data", None))(y))


def test_gather_transpose_is_reduce_scatter(fabs):
    """FSDP identity: grad through ring-AG == dense grad (the paper's
    Fig 3 RS traffic is the transpose of the AG)."""
    fab, _, mesh = fabs
    x = jnp.arange(32.).reshape(32, 1) + 1
    t = jnp.cos(jnp.arange(32.)).reshape(32, 1)

    def loss(w_shard, t_shard):
        w = fab.all_gather(w_shard)
        i = jax.lax.axis_index("data")
        wl = jax.lax.dynamic_slice_in_dim(w, i * 4, 4, 0)
        return jnp.sum(jnp.sin(wl) * t_shard)

    g = smap(mesh, jax.grad(loss), (P("data", None), P("data", None)),
             P("data", None))(x, t)
    g_ref = jax.grad(lambda w: jnp.sum(jnp.sin(w) * t))(x)
    np.testing.assert_allclose(g, g_ref, atol=1e-5)


def test_hierarchical_two_axis_gather(mesh_pod):
    fab = Fabric(("pod", "data"), (2, 2), "photonic")
    x = jnp.arange(16.).reshape(16, 1)
    f = jax.jit(jax.shard_map(fab.all_gather, mesh=mesh_pod,
                              in_specs=P(("pod", "data"), None),
                              out_specs=P(None, None),
                              axis_names={"pod", "data"}, check_vma=False))
    np.testing.assert_array_equal(f(x)[:16], x)


def test_shift_is_circuit_legal_permutation(fabs):
    fab, _, mesh = fabs
    x = jnp.arange(8.).reshape(8, 1)
    y = smap(mesh, lambda s: fab.shift(s, 1), P("data", None),
             P("data", None))(x)
    np.testing.assert_array_equal(np.asarray(y).ravel(),
                                  np.roll(np.arange(8.), 1))
