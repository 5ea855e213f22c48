"""Compile rehearsals: every Pallas kernel lowered by Mosaic for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described v5e
topology, and refuses here what it would refuse on the chip (tile shapes
off the (8, 128) grid, scalar stores to VMEM, misaligned lane slices, a
footprint past the VMEM limit).  Interpret mode checks none of that.
Each test compiles one kernel at a real width with ``interpret=False``
and asserts the Mosaic kernel (``tpu_custom_call``) is in the program.

The topology is described inside a module-scoped fixture, never while a
module is imported, and the persistent compilation cache is off around
the compiles (an entry written for a described device cannot be read
back without one).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import tiles
from repro.kernels.assignment import assignment_pallas
from repro.kernels.fused_lloyd import fused_lloyd_pallas
from repro.kernels.update import update_pallas

# (N, K, d, dtype): a Table-1-like small-d shape, an IVF coarse quantizer
# at SIFT width in f32 and bf16, and K = 65,536 at Deep width (the
# resident (K, d) accumulator then needs a raised VMEM limit)
WIDTHS = [
    (65_536, 256, 16, jnp.float32),
    (262_144, 4096, 128, jnp.float32),
    (262_144, 4096, 128, jnp.bfloat16),
    (65_536, 65_536, 96, jnp.float32),
]
IVF = (262_144, 4096, 128, jnp.float32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


def _ids(widths):
    return [f"{n}x{k}x{d}-{jnp.dtype(t).name}" for n, k, d, t in widths]


@pytest.mark.parametrize("n,k,d,dtype", WIDTHS, ids=_ids(WIDTHS))
def test_fused_compiles(one_chip, no_cache, n, k, d, dtype):
    _compile(lambda x, c: fused_lloyd_pallas(x, c, interpret=False),
             _spec(one_chip, (n, d), dtype), _spec(one_chip, (k, d), dtype))


@pytest.mark.parametrize("n,k,d,dtype", [WIDTHS[0], IVF],
                         ids=_ids([WIDTHS[0], IVF]))
def test_fused_batched_compiles(one_chip, no_cache, n, k, d, dtype):
    _compile(lambda x, c, w: fused_lloyd_pallas(x, c, w, interpret=False),
             _spec(one_chip, (n, d), dtype),
             _spec(one_chip, (4, k, d), dtype), _spec(one_chip, (4, n)))


@pytest.mark.parametrize("r", [1, 4])
def test_assignment_compiles(one_chip, no_cache, r):
    n, k, d, dtype = IVF
    c_shape = (k, d) if r == 1 else (r, k, d)
    _compile(lambda x, c: assignment_pallas(x, c, interpret=False),
             _spec(one_chip, (n, d), dtype), _spec(one_chip, c_shape, dtype))


def test_update_compiles(one_chip, no_cache):
    n, k, d, dtype = IVF
    _compile(lambda x, lab, w: update_pallas(x, lab, k, w=w, interpret=False),
             _spec(one_chip, (n, d), dtype),
             _spec(one_chip, (n,), jnp.int32), _spec(one_chip, (n,)))


def test_fused_bounds_compiles(one_chip, no_cache):
    n, k, d, dtype = IVF
    tk = tiles.MAX_TILE
    g = k // tk
    _compile(lambda x, c, lab, lb, ub: fused_lloyd_pallas(
                 x, c, tk=tk, interpret=False, bounds=(lab, lb, ub)),
             _spec(one_chip, (n, d), dtype), _spec(one_chip, (k, d), dtype),
             _spec(one_chip, (n,), jnp.int32), _spec(one_chip, (n, g)),
             _spec(one_chip, (n,)))


def test_shrunk_k_tile_compiles(one_chip, no_cache):
    """A budget that forces the chooser below the full k extent still
    yields lane-legal tiles the compiler accepts."""
    n, k, d = 65_536, 1000, 9
    budget = 1 << 20
    tn, tk = tiles.choose_tiles(n, k, d, 4, kind="fused", vmem_bytes=budget)
    assert tk < tiles.round_up(k, 8) and tk % tiles.LANE == 0, (tn, tk)
    _compile(lambda x, c: fused_lloyd_pallas(x, c, interpret=False,
                                             vmem_bytes=budget),
             _spec(one_chip, (n, d)), _spec(one_chip, (k, d)))


def test_distributed_fused_step_compiles(topo, no_cache, monkeypatch):
    """The fused step under shard_map over a 4-chip mesh, with the
    varying-manual-axes checker on as on the chip: the kernel's outputs
    carry their vma.  The kernels are steered to their compiled path in
    the test, since this host's default backend is the CPU."""
    from repro.core import backends as B
    from repro.core.distributed import shard_map
    monkeypatch.setattr(tiles, "interpret_default", lambda: False)
    n, k, d, dtype = IVF
    mesh = jax.sharding.Mesh(topo.devices[:4], ("data",))
    dist = B.distribute(B.get_backend("fused"), ("data",))
    step = shard_map(
        lambda xl, cc: dist.step(xl, cc, k, ())[0], mesh=mesh,
        in_specs=(P("data"), P()),
        out_specs=B.StepResult(labels=P("data"), min_sqdist=P("data"),
                               sums=P(), counts=P(), energy=P()),
        check_vma=True)
    compiled = _compile(step,
                        _spec(NamedSharding(mesh, P("data")), (n, d), dtype),
                        _spec(NamedSharding(mesh, P()), (k, d), dtype))
    assert "all-reduce" in compiled.as_text()


def test_oversized_accumulator_is_refused():
    """K*d whose resident f32 stats exceed one TensorCore's VMEM is
    refused by the wrapper with a message that names the limit; there is
    no fallback path."""
    x = jax.ShapeDtypeStruct((4096, 200), jnp.float32)
    c = jax.ShapeDtypeStruct((65_536, 200), jnp.float32)
    with pytest.raises(ValueError, match="MiB of VMEM"):
        jax.eval_shape(lambda a, b: fused_lloyd_pallas(a, b, interpret=False),
                       x, c)
