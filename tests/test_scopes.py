"""Named scopes (``repro.scopes``): registered names only, and each reaches
the compiled HLO's ``op_name`` of the work it names."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core._fabric_rings import Fabric
from repro.scopes import SCOPES, scope


def test_scope_refuses_unregistered_names():
    with pytest.raises(ValueError, match="unregistered scope"):
        with scope("ssd_fwdd"):
            pass
    with scope("ssd_fwd"):
        pass


def _op_names(compiled_text: str, opcode: str):
    return [m.group(1) for m in re.finditer(
        rf" {opcode}\(.*?op_name=\"([^\"]*)\"", compiled_text)]


def test_scope_names_compiled_ops():
    """Forward and backward ops carry their scopes, nested, the backward's
    inside JAX's transform wrappers (``transpose(jvp(layer))``)."""
    def f(x):
        with scope("layer"):
            y = jnp.sin(x)
            with scope("lm_head"):
                return jnp.sum(y @ x)

    text = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8))).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert "jit(f)/jvp(layer)/sin" in names
    assert "jit(f)/transpose(jvp(layer))/lm_head/dot_general" in names


@pytest.mark.parametrize("collective,want", [
    ("all_gather", "ring_gather"),
    ("reduce_scatter", "ring_scatter"),
    ("all_reduce", "ring_all_reduce"),
    ("all_to_all", "ring_all_to_all"),
])
def test_ring_collectives_carry_their_own_scope(mesh_data8, collective,
                                                want):
    """Each ring's permutes sit under its own scope, and not under the
    scope of the ring it is built from (a reduce-scatter is the transpose
    of a gather, an all-reduce a scatter then a gather)."""
    fab = Fabric(("data",), (8,), "photonic")
    fn = getattr(fab, collective)
    shape = (64, 8, 4) if collective == "all_to_all" else (64, 4)
    f = jax.jit(jax.shard_map(fn, mesh=mesh_data8,
                              in_specs=jax.sharding.PartitionSpec("data"),
                              out_specs=jax.sharding.PartitionSpec("data"),
                              check_vma=False))
    text = f.lower(jnp.ones(shape)).compile().as_text()
    names = _op_names(text, "collective-permute(?:-start)?")
    assert names
    rings = {s for s in SCOPES if s.startswith("ring_")}
    for n in names:
        on_path = [p for p in n.split("/") if p in rings]
        assert on_path and on_path[-1] == want, n
