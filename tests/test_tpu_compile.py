"""The DTW Pallas kernels compile for a TPU v5e at the smoke shapes.

Interpret mode runs a kernel body in Python and accepts what the chip's
compiler refuses (rank-1 blocks, unaligned lane shifts, VMEM over the
limit).  These tests compile each kernel of the serving path for a
described, unattached ``v5e:2x2`` chip at the shapes ``chip_smoke.py``
serves (S jobs x K=1024 references x M=512 samples), and check that the
compiled program holds the Mosaic kernel (``tpu_custom_call``) and fits
one chip's 16 GB of HBM.  The four-chip deployment's tick and verdict
(a 4,096-run bank K-sharded over the 2x2 host) compile over the four
chips and fit each, while the same tick on one chip does not.  Nothing
runs, so results are not checked here (the interpret-mode equivalence
suites pin them).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import dtw as _dtw
from repro.kernels.dtw import (score_bank_offline_kernel,
                               score_bank_offline_var_kernel)

K, M, C, N = 1024, 512, 8, 512        # bank, reference length, chunk, query
HBM_BYTES = 16e9                      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out of the cache while these tests run.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _check(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"


@pytest.mark.parametrize("band", [None, 8], ids=["full", "band8"])
@pytest.mark.parametrize("nch,s", [(0, 256), (3, 256), (4, 128), (6, 128)],
                         ids=["distance", "scored", "approx", "exact"])
def test_stream_tick_compiles(spec, nch, s, band):
    """The serving tick in tick layout ([S, M, K] state), as the service
    dispatches it through ``bank_extend_tick``: no moment slab
    (distance), 3 channels (scored), 4 (approx probability) and 6
    (exact probability)."""
    i32 = jnp.int32
    var = nch > 3
    args = (spec((s, M, K)), spec((nch, s, M, K)) if nch else None,
            spec((s,), i32), spec((s,)), spec((s,)),           # ns, sx, sxx
            spec((s, 3)) if var else None,                     # vstats
            spec((M, K)), spec((K,), i32),                     # bank
            spec((s, C)), spec((s, C)) if var else None,       # chunks
            spec((s,), i32), spec((s,), i32))                  # nvalid, qlens

    def fn(*a):
        return _dtw.bank_extend_tick(*a, band=band, use_kernel=True,
                                     interpret=False)
    _check(fn, *args)


@pytest.mark.parametrize("band", [None, 8], ids=["full", "band8"])
@pytest.mark.parametrize("nch,j", [(3, 32), (6, 16)],
                         ids=["scored", "exact"])
def test_offline_verdict_compiles(spec, nch, j, band):
    """The finish-path verdict scorer: 3 channels (point score) and 6
    (exact probability), J complete queries of up to N samples."""
    i32 = jnp.int32
    queries = (spec((j, N)),) if nch == 3 else (spec((j, N)), spec((j, N)))
    args = (*queries, spec((j,), i32), spec((K, M)), spec((K,), i32),
            spec((j,)), spec((j,)))
    if nch == 3:
        def fn(*a):
            return score_bank_offline_kernel(*a, band=band, interpret=False)
    else:
        args += (spec((j, 3)),)

        def fn(*a):
            return score_bank_offline_var_kernel(
                *a, band=band, threshold=0.9, interpret=False)
    _check(fn, *args)


# -- the four-chip deployment: a 4,096-run bank K-sharded over a 2x2 host --

K4, S4, J4 = 4096, 256, 32


@pytest.fixture(scope="module")
def mesh4(topo):
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(topo.devices[:4]), ("bank",),
        axis_types=(jax.sharding.AxisType.Auto,))


def _planned(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _tick_args(make, k):
    """The scored tick's arguments; ``make(shape, k_axis, dtype)``."""
    i32 = jnp.int32
    return (make((S4, M, k), 2), make((3, S4, M, k), 3),
            make((S4,), None, i32), make((S4,)), make((S4,)),
            make((M, k), 1), make((k,), 0, i32),
            make((S4, C)), make((S4,), None, i32), make((S4,), None, i32))


def test_sharded_tick_and_verdict_fit_four_chips(mesh4):
    """The scored tick shard_mapped over the bank axis (each chip runs
    the Mosaic kernel on its 1,024 references) and the K-sharded verdict
    both compile for the 2x2 host, and each chip's planned bytes fit its
    16 GB."""
    from repro.serve.tuning import tick_program
    P = jax.sharding.PartitionSpec

    def make(shape, k_axis=None, dtype=jnp.float32):
        spec = [None] * len(shape)
        if k_axis is not None:
            spec[k_axis] = "bank"
        return jax.ShapeDtypeStruct(shape, dtype, sharding=(
            jax.sharding.NamedSharding(mesh4, P(*spec))))

    tick = tick_program("scored", mesh4, band=8, use_kernel=True,
                        interpret=False)
    compiled = tick.lower(*_tick_args(make, K4)).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    assert compiled.input_shardings[0][0].num_devices == 4
    assert _planned(compiled) < HBM_BYTES, \
        f"{_planned(compiled) / 1e9:.2f} GB does not fit one chip"

    verdict = _dtw._sharded_offline(mesh4, False, False, 8, 0.9, 128, False)
    i32 = jnp.int32
    compiled = verdict.lower(
        make((J4, N)), make((J4,), None, i32), make((K4, M), 0),
        make((K4,), 0, i32), make((J4,)), make((J4,))).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    assert compiled.input_shardings[0][2].num_devices == 4
    assert _planned(compiled) < HBM_BYTES


def test_unsharded_tick_does_not_fit_one_chip(spec):
    """The same tick over all 4,096 references on one chip needs more
    than its 16 GB (the compiler refuses it): the deployment needs the
    four chips."""
    from jax.errors import JaxRuntimeError
    from repro.serve.tuning import tick_program

    def make(shape, k_axis=None, dtype=jnp.float32):
        return spec(shape, dtype)

    tick = tick_program("scored", band=8, use_kernel=True, interpret=False)
    try:
        compiled = jax.jit(tick).lower(*_tick_args(make, K4)).compile()
    except JaxRuntimeError as e:
        assert "RESOURCE_EXHAUSTED" in str(e), e
        return
    assert _planned(compiled) > HBM_BYTES, \
        f"{_planned(compiled) / 1e9:.2f} GB fits one chip"
