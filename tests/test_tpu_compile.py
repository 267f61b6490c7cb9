"""Ahead-of-time compiles of the provisioning kernels for a described v5e.

The chip's compiler is installed with jaxlib, so the Pallas kernels can be
compiled for a TPU v5e that is described, not attached.  Interpret-mode
parity tests cannot see what Mosaic refuses (sub-tile block shapes, bool
loop carries, sub-tile slices of SMEM rings); these compiles can.  Shapes
are the paper's MSR deployment: N = 10,240 levels, T = 4,320 slots, W = 6
windows, Delta = 6.

The topology is described inside a module-scoped fixture only: describing
it loads the TPU library, which one process at a time may hold.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

N, T, W, DELTA = 10_240, 4_320, 6, 6


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture
def aot(monkeypatch):
    """Force the compiled Pallas route although the backend is the CPU,
    and keep these compiles out of any persistent compile cache (a
    described chip's program cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _kernel_args(sharding, time_varying):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (
        s((1, T), jnp.int32),                               # demand rows
        s((1, T), jnp.int32),                               # predicted rows
        s((W, T if time_varying else 1, N), jnp.float32),   # thresholds
        *(s((W,), jnp.int32) for _ in range(4)),            # cell maps
        s((W, N), jnp.float32),                             # horizon rows
        s((N,), jnp.int32),                                 # routes
    )


_KERNEL_CASES = {
    "grid-constant": ("grid", False, False),
    "grid-time_varying": ("grid", True, False),
    "stream-constant": ("stream", False, False),
    "stream-time_varying": ("stream", True, False),
    # record=True carries seven per-lane accumulators through the slot loop
    # beside the on-tile store; the tile's MXU lane sum must fit there too
    "stream-constant-record": ("stream", False, True),
    "stream-time_varying-record": ("stream", True, True),
}


@pytest.mark.parametrize(("kernel", "time_varying", "record"),
                         list(_KERNEL_CASES.values()), ids=list(_KERNEL_CASES))
def test_kernel_compiles_for_v5e(topo, aot, kernel, time_varying, record):
    from repro.kernels.provision_scan import (
        provision_scan_grid,
        provision_scan_stream,
    )

    def run(a, p, m, ct, cp, cthr, chor, hor, routes):
        kw = dict(horizon=DELTA, routes=routes, level_horizon=hor,
                  interpret=False, record=record)
        if kernel == "grid":
            return provision_scan_grid(a, p, m, ct, cp, cthr, chor,
                                       delta=DELTA, **kw)
        return provision_scan_stream(a, p, m, ct, cp, cthr, chor, **kw)

    args = _kernel_args(SingleDeviceSharding(topo.devices[0]), time_varying)
    text = jax.jit(run).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's name is the custom call's instruction name, which is
    # what a device trace's op events carry
    assert f"%provision_scan_{kernel}" in text


def test_sharded_stream_grid_compiles_on_four_chips(topo, aot):
    from repro.core.jax_provision import _sharded_stream_grid

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    rep = NamedSharding(mesh, P())

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    lowered = _sharded_stream_grid.lower(
        s((1, T), jnp.int32), s((1, 1, T), jnp.int32), s((W,), jnp.int32),
        s((N,), jnp.float32), s((N,), jnp.float32), s((N,), jnp.float32),
        s((N,), jnp.float32), None,
        mesh=mesh, axis="data", n_levels=N, max_h=DELTA, h_unroll=DELTA,
        policy="A1", use_pallas=True, t_chunk=512,
    )
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text            # x(t) is a psum over the shards


def test_sharded_stream_grid_compiles_typed4_aqrand_on_one_chip(topo, aot):
    """The typed fleet's route on one chip: four server generations of one
    Google cell (12,391 levels) in the group-aligned layout, 99 blocks of
    128 lanes, and AQ-rand's keyed (1, T, 12,672) threshold table, built
    from the draws in the same program, over a week of ten-minute slots."""
    from repro.core.jax_provision import _sharded_stream_grid

    groups, t_week = (6732, 3863, 1001, 795), 1_008
    n = sum(groups)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    rep = NamedSharding(mesh, P())

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 1))
    lowered = _sharded_stream_grid.lower(
        s((1, t_week), jnp.int32), s((1, 1, t_week), jnp.int32), s((1,), jnp.int32),
        s((n,), jnp.float32), s((n,), jnp.float32), s((n,), jnp.float32),
        s((n,), jnp.float32), s(keys.shape, keys.dtype),
        mesh=mesh, axis="data", n_levels=n, max_h=DELTA, h_unroll=0,
        policy="AQ-rand", use_pallas=True, group_sizes=groups, t_chunk=512,
    )
    text = lowered.compile().as_text()
    assert "%provision_scan_stream" in text
