"""The main path compiled for a described TPU v5e — no chip needed.

JAX can compile for a TPU topology it only describes: the TPU compiler runs
here, nothing executes.  These tests compile the programs the chip runs at
deployment size (Graph500 R-MAT scale 20: N = 2^20 vertices, a 2^24-slot
edge pool) so that whatever Mosaic or XLA:TPU refuses, or whatever does
not fit the chip's memory, fails here first.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles (a cached entry for a described chip cannot be read back).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import frontier, relax
from repro.core.backends.sliced import (SlicedEllPlanner, SlicedEllState,
                                        sliced_relax_wave)
from repro.core.dist_engine import _build_epochs
from repro.core.distributed import DistConfig, DistributedSSSP
from repro.core.state import EdgePool, SSSPState
from repro.kernels.relax import config as kernel_config
from repro.kernels.relax.fused import fused_sliced_relax
from repro.kernels.relax.gather import gathered_rows_relax
from repro.kernels.relax.relax import ellpack_relax

N = 1 << 20          # Graph500 scale 20
POOL = 1 << 24       # edge pool: 16.8M R-MAT edges before dedup
HBM = 16 * 10**9     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_relax_until_converged_compiles_at_deployment_size(one_chip):
    """The default segment backend's epoch: N = 2^20, a 2^24 pool."""
    f32, i32 = jnp.float32, jnp.int32
    sssp = SSSPState(_sds((N,), f32, one_chip), _sds((N,), i32, one_chip),
                     _sds((), i32, one_chip))
    pool = EdgePool(_sds((POOL,), i32, one_chip), _sds((POOL,), i32, one_chip),
                    _sds((POOL,), f32, one_chip),
                    _sds((POOL,), jnp.bool_, one_chip))
    compiled = relax.relax_until_converged.lower(
        sssp, pool, _sds((N,), jnp.bool_, one_chip),
        num_vertices=N).compile()
    assert _device_bytes(compiled) < HBM


@pytest.mark.parametrize("epoch", ["add", "del"])
def test_ladder_epochs_compile_at_deployment_size(one_chip, epoch):
    """The default route's epochs (core/frontier.py): the ADD epoch's seed
    wave over a 2^13-edge batch and the DEL epoch's recompute, each through
    the capacity ladder, with a sidecar of 2^25 cells and a 2^23-entry
    overflow lane."""
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    L, C, B = 1 << 25, 1 << 23, 1 << 13
    sssp = SSSPState(_sds((N,), f32, one_chip), _sds((N,), i32, one_chip),
                     _sds((), i32, one_chip))
    pool = EdgePool(_sds((POOL,), i32, one_chip), _sds((POOL,), i32, one_chip),
                    _sds((POOL,), f32, one_chip), _sds((POOL,), b, one_chip))
    st = frontier.OutState(*(_sds(s, t, one_chip) for s, t in (
        ((L,), i32), ((L,), f32), ((N,), i32), ((N,), i32), ((C,), i32),
        ((C,), i32), ((C,), f32), ((N,), i32))))
    caps = frontier.capacity_ladder(N)
    if epoch == "add":
        lowered = frontier.seeded_relax.lower(
            sssp, pool, st, _sds((B,), i32, one_chip),
            _sds((B,), i32, one_chip), _sds((B,), f32, one_chip),
            num_vertices=N, caps=caps)
    else:
        lowered = frontier.sparse_invalidate_and_recompute.lower(
            sssp, pool, st, _sds((N,), b, one_chip), num_vertices=N,
            caps=caps)
    assert _device_bytes(lowered.compile()) < HBM


def _rmat_in_degrees(scale: int, edgefactor: int) -> np.ndarray:
    """In-degrees with the expected profile of Graph500 R-MAT: a dst bit is
    1 with probability b + d = 0.24, independently per bit."""
    n = 1 << scale
    ones = np.array([bin(v).count("1") for v in range(n)], np.int64)
    mean = n * edgefactor * 0.76 ** (scale - ones) * 0.24 ** ones
    return np.random.default_rng(0).poisson(mean)


def test_sliced_wave_compiles_at_rmat_widths(one_chip):
    """The sliced backend's jitted wave with the slice widths and overflow
    capacity its planner picks for R-MAT scale 20."""
    deg = _rmat_in_degrees(20, 16)
    planner = SlicedEllPlanner(N)
    widths, ocap = planner.required_geometry(
        np.repeat(np.arange(N, dtype=np.int64), deg))
    planner.widths, planner.ocap = widths, ocap
    planner._recompute_geometry()
    assert max(widths) == planner.hub_k and ocap > 1 << 20  # hubs spill
    L, R = planner.cells, planner.rows
    f32, i32 = jnp.float32, jnp.int32
    st = SlicedEllState(*(_sds(s, t, one_chip) for s, t in (
        ((L,), i32), ((L,), f32), ((R,), i32), ((R,), i32), ((R,), i32),
        ((ocap,), i32), ((ocap,), i32), ((ocap,), f32))))
    compiled = sliced_relax_wave.lower(
        _sds((N,), f32, one_chip), _sds((N,), i32, one_chip), st,
        widths=tuple(widths), slice_rows=planner.sr, num_vertices=N,
        frontier=_sds((N,), jnp.bool_, one_chip)).compile()
    assert _device_bytes(compiled) < HBM


def test_ellpack_kernel_compiles_for_tpu(one_chip):
    """The one Pallas relax kernel Mosaic accepts, at N = 2^20, K = 32."""
    K = 32
    fn = functools.partial(ellpack_relax, interpret=False)
    compiled = jax.jit(fn).lower(
        _sds((N,), jnp.float32, one_chip), _sds((N, K), jnp.int32, one_chip),
        _sds((N, K), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("knob", sorted(kernel_config.REFUSED_ON_TPU))
def test_refused_kernels_are_still_refused(one_chip, knob):
    """``REFUSED_ON_TPU`` records what Mosaic says today; when a jax
    release lowers one of these kernels, this fails and the kernel can go
    back on the table."""
    n, e = 1 << 12, 1 << 12
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    if knob == "sliced_fused":
        fn = functools.partial(fused_sliced_relax, widths=(8,) * (n // 256),
                               slice_rows=256, interpret=False)
        args = ((n,), f32), ((n,), b), ((n * 8,), i32), ((n * 8,), f32), \
            ((e,), i32), ((e,), i32), ((e,), f32)
    else:
        fn = functools.partial(gathered_rows_relax, num_rows=n,
                               interpret=False)
        args = ((e,), f32), ((e,), i32), ((e,), i32), ((e,), f32), ((e,), b)
    with pytest.raises(NotImplementedError) as err:
        jax.jit(fn).lower(*(_sds(s, t, one_chip) for s, t in args)).compile()
    reason = str(err.value).splitlines()[0].split(". Please")[0]
    assert reason in kernel_config.REFUSED_ON_TPU[knob], reason


def test_sharded_add_epoch_compiles_on_four_chips(topo):
    """One sharded ADD epoch (segment backend, allgather exchange) on a
    described 2x2 v5e mesh, pools sized as chip_smoke.py --four-chips
    sizes them (relabeled R-MAT scale 20: 2^22 slots per partition)."""
    P, epp, B = 4, 1 << 22, 1 << 20
    mesh = Mesh(np.array(topo.devices[:P]), ("graph",))
    ds = DistributedSSSP(mesh, DistConfig(num_vertices=N, edges_per_part=epp,
                                          mesh_axes=("graph",)))
    add_epoch, _, _ = _build_epochs(ds, epp, True, 0, "segment",
                                    ("segment",))
    v = NamedSharding(mesh, ds.vspec)
    r = NamedSharding(mesh, PartitionSpec())
    f32, i32 = jnp.float32, jnp.int32
    args = [_sds((N,), f32, v), _sds((N,), i32, v),
            _sds((P * epp,), i32, v), _sds((P * epp,), i32, v),
            _sds((P * epp,), f32, v), _sds((P * epp,), jnp.bool_, v),
            _sds((B,), i32, r), _sds((B,), i32, r), _sds((B,), i32, r),
            _sds((B,), f32, r), _sds((), i32, r), _sds((), i32, r)]
    compiled = add_epoch.lower(*args).compile()
    assert "all-gather" in compiled.as_text()
    assert _device_bytes(compiled) < HBM
