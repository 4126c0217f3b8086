"""Checkpointing: roundtrip, atomic commits, keep-k GC, async save, and
elastic restore across a different mesh (subprocess, 8 devices)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16)),
                   "layers": [{"a": jnp.ones((4,))}, {"a": jnp.zeros((4,))}]},
        "opt": {"step": jnp.asarray(7, jnp.int32)},
    }


def test_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    s = _state()
    ck.save(3, s)
    restored, step = ck.restore(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s))
    assert step == 3
    for a, b in zip(jax.tree_util.tree_leaves(s),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_k_gc(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _state(s))
    assert sorted(ck.all_steps()) == [3, 4]


def test_async_save(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(10, _state(), blocking=False)
    ck.wait()
    assert ck.latest_step() == 10


def test_tmp_dirs_ignored(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(5, _state())
    (tmp_path / "step_000000009.tmp").mkdir()   # simulated crash mid-save
    assert ck.latest_step() == 5


def test_shape_mismatch_rejected(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, {"w": jnp.ones((4,))})
    with pytest.raises(ValueError):
        ck.restore({"w": jax.ShapeDtypeStruct((5,), jnp.float32)})


def test_elastic_restore_across_meshes(devices8, tmp_path):
    """Save sharded on a (4,2) mesh, restore onto (2,4) — the elastic
    restart path (device loss -> different mesh)."""
    code = f"""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import Checkpointer

mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
w_a = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
ck = Checkpointer(r'{tmp_path}')
ck.save(1, {{"w": w_a}})

mesh_b = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sh_b = {{"w": NamedSharding(mesh_b, P("data", "model"))}}
restored, step = ck.restore(
    {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}, shardings=sh_b)
assert step == 1
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
assert restored["w"].sharding.mesh.shape["model"] == 4
print("ELASTIC_OK")
"""
    out = devices8(code)
    assert "ELASTIC_OK" in out
