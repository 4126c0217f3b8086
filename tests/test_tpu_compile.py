"""Native TPU compiles for a described (not attached) v5e.

Interpret mode never checks Mosaic's block-shape and memory rules; these
compiles do, at the flagship 4096×256 size over 32 banks, and assert the
kernel is really in the program (``tpu_custom_call``).  The topology is
described inside a fixture, so only the worker that runs this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.params import DimaParams
from repro.kernels import ops

P = DimaParams()
M, N_BANKS, B = 4096, 32, 8
TRIM = (0.97, 0.01, 3.0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("trim", [None, TRIM], ids=["plain", "trim"])
@pytest.mark.parametrize("op", ["matvec", "matmat"])
@pytest.mark.parametrize("mode", ["dp", "md"])
def test_banked_kernel_compiles(one_chip, mode, op, trim):
    f = getattr(ops, f"dima_{mode}_bank_{op}")
    d = jax.ShapeDtypeStruct((N_BANKS, M // N_BANKS, 256), jnp.uint8,
                             sharding=one_chip)
    q = jax.ShapeDtypeStruct((256,) if op == "matvec" else (B, 256),
                             jnp.uint8, sharding=one_chip)
    text = _compiled_text(
        lambda dd, qq: f(dd, qq, P, interpret=False, trim=trim), d, q)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["matvec", "matmat"])
@pytest.mark.parametrize("mode", ["dp", "md"])
def test_multibank_step_compiles(one_chip, mode, op):
    """The multibank backend's one execution per call: stored rows
    (2048, 256) split into 32 banks of 64, cast, padded, drawn for,
    launched and merged inside ``_bank_call``, with a (lo, hi) window
    and a trim triple as operands (no key: the noise stacks are the
    banked kernels' own, and compile for tens of seconds)."""
    rows = jax.ShapeDtypeStruct((2048, 256), jnp.uint8, sharding=one_chip)
    q = jax.ShapeDtypeStruct((256,) if op == "matvec" else (B, 256),
                             jnp.uint8, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    trim = jax.ShapeDtypeStruct((3,), jnp.float32, sharding=one_chip)
    lowered = ops._bank_call.lower(
        rows, q, None, None, (f32, f32), trim, 0, params=P,
        interpret=False, mode=mode, matvec=op == "matvec", rows_per=64)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["banked", "matmat"])
@pytest.mark.parametrize("mode", ["dp", "md"])
def test_single_bank_kernel_compiles(one_chip, mode, op):
    f = getattr(ops, f"dima_{mode}_{op}")
    d = jax.ShapeDtypeStruct((M, 256), jnp.uint8, sharding=one_chip)
    q = jax.ShapeDtypeStruct((256,) if op == "banked" else (B, 256),
                             jnp.uint8, sharding=one_chip)
    text = _compiled_text(
        lambda dd, qq: f(dd, qq, P, interpret=False, trim=TRIM), d, q)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["matvec", "matmat"])
def test_mesh_shard_map_compiles(topo, op):
    """The multibank mesh path: one banked kernel per device of a
    described 2x2 mesh, 8 banks each."""
    from repro.core.api import MultiBankBackend, PallasBackend
    mesh = Mesh(np.asarray(topo.devices), ("banks",))
    be = MultiBankBackend(P, inner=PallasBackend(P, interpret=False),
                          n_banks=N_BANKS, mesh=mesh)
    f = be._mesh_fn(op, "dp", True, False, True)
    rep = NamedSharding(mesh, PartitionSpec())
    args = (
        jax.ShapeDtypeStruct((N_BANKS, M // N_BANKS, 256), jnp.uint8,
                             sharding=NamedSharding(mesh,
                                                    PartitionSpec("banks"))),
        jax.ShapeDtypeStruct((256,) if op == "matvec" else (B, 256),
                             jnp.uint8, sharding=rep),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        jax.ShapeDtypeStruct((2,), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((3,), jnp.float32, sharding=rep),
    )
    compiled = f.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
