"""Compile every Pallas kernel for a described TPU v5e, at the widths
``chip_smoke.py`` runs (DREAM5-Insilico: n=1643, m=850), with no chip
attached.

The TPU compiler is installed with libtpu: a topology description stands in
for the device, ``interpret=False`` forces the Mosaic lowering, and the
compiled HLO must carry one ``tpu_custom_call`` per kernel. This catches what
interpret mode cannot (unsupported primitives, bool/8-bit relayouts, block
tiling) without chip time. Only one process may load libtpu, so the topology
is described inside a module fixture (never at import) and every compile
stays in this file.
"""
import functools
import re

import pytest

pytestmark = pytest.mark.kernels

N, M = 1643, 850  # chip_smoke.py's DREAM5-Insilico size
# (ℓ, n_chunk, n′ bucket) of chunk programs at this n, from the level stats
# of the chip runs: S-kernel at ℓ=2 (n′ 172 → 256) and S-grid at ℓ=1, where a
# row's 1643 slots span 13 slot blocks of the grid kernel
S_KERNEL_KEY = (2, 8, 256)
S_GRID_KEY = (1, 16, N)
MOSAIC = re.compile(r'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(dev, shape, dtype="float32"):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=dev)


def _corr(dev):
    from repro.kernels import ops

    fn = functools.partial(ops.correlation, interpret=False)
    return fn, (_spec(dev, (M, N)),)


def _level0(dev):
    from repro.kernels import ops

    fn = functools.partial(ops.level0, interpret=False)
    return fn, (_spec(dev, (N, N)), _spec(dev, ()))


def _level1(dev):
    from repro.kernels import ops

    fn = functools.partial(ops.level1_dense, interpret=False)
    return fn, (_spec(dev, (N, N)), _spec(dev, (N, N), "bool"), _spec(dev, ()))


def _cholinv_cisweep(dev):
    """ops.ci_shared = cholinv + cisweep over one S-kernel chunk."""
    from repro.kernels import ops

    ell, n_chunk, npr = S_KERNEL_KEY
    b = N * n_chunk
    fn = functools.partial(ops.ci_shared, ell=ell, interpret=False)
    return fn, (_spec(dev, (b, ell, ell)), _spec(dev, (b, ell)),
                _spec(dev, (b, npr, ell)), _spec(dev, (b, npr)),
                _spec(dev, (b, npr), "bool"), _spec(dev, ()))


def _sgrid(dev):
    from repro.kernels import ops

    ell, t, npr = S_GRID_KEY
    fn = functools.partial(ops.ci_shared_grid, ell=ell, interpret=False)
    return fn, (_spec(dev, (N, t, ell, ell)), _spec(dev, (N, t, ell)),
                _spec(dev, (N, t, npr, ell)), _spec(dev, (N, t, npr)),
                _spec(dev, (N, t, npr), "bool"), _spec(dev, (N, t, ell), "int32"),
                _spec(dev, ()))


def _gsq(dev):
    """Discrete G² cells at arity 3, ℓ=2 (K = 81 table slots), m samples."""
    from repro.kernels import gsq

    fn = functools.partial(gsq.gsq_cells, r=3, q=9, interpret=False)
    return fn, (_spec(dev, (M, 4096), "int32"),)


# (ℓ, rows bucket, n_chunk, n′ bucket) of the degree-class programs of the
# nci60 benchmark's timed graphs (n=1190): the hub class, a mid class, the
# many-row low-degree class at ℓ=2, and the one class of ℓ=3
NCI60_N = 1190
CLASS_KEYS = [(2, 8, 1024, 64), (2, 64, 512, 32), (2, 256, 8, 4), (3, 8, 4, 4)]


@pytest.mark.parametrize("key", CLASS_KEYS, ids=[
    "l{}_rows{}_t{}_npr{}".format(*k) for k in CLASS_KEYS])
def test_degree_class_program_compiles_for_v5e(one_chip, monkeypatch, key):
    """One degree-class program of the S-kernel route at ℓ ≥ 2 (XLA
    compaction and gathers, cholinv + cisweep, the claim scatters), whole,
    as the chip runs it."""
    import jax

    from repro.core import levels as L
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interp", lambda flag=None: False)
    ell, rows_b, n_chunk, npr = key
    n = NCI60_N
    fn = functools.partial(ops.chunk_s_kernel_class.__wrapped__, ell=ell,
                           n_chunk=n_chunk, n_max=npr)
    rank = L._rank_dtype().dtype.name
    compiled = jax.jit(fn).lower(
        _spec(one_chip, (n, n)), _spec(one_chip, (n, n), "bool"),
        _spec(one_chip, (n, n, 8), "int32"), _spec(one_chip, (n, n), rank),
        _spec(one_chip, (rows_b,), "int32"), _spec(one_chip, (), rank),
        _spec(one_chip, ())).compile()
    assert len(MOSAIC.findall(compiled.as_text())) == 2


@pytest.mark.parametrize("build,kernels", [
    (_corr, 1), (_level0, 1), (_level1, 1), (_cholinv_cisweep, 2),
    (_sgrid, 1), (_gsq, 1),
], ids=["corr", "level0", "level1", "cholinv+cisweep", "sgrid", "gsq"])
def test_kernel_compiles_for_v5e(one_chip, build, kernels):
    import jax

    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert len(MOSAIC.findall(compiled.as_text())) == kernels
