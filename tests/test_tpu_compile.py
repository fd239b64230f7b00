"""The DTW Pallas kernels compile for a TPU v5e at the smoke shapes.

Interpret mode runs a kernel body in Python and accepts what the chip's
compiler refuses (rank-1 blocks, unaligned lane shifts, VMEM over the
limit).  These tests compile each kernel of the serving path for a
described, unattached ``v5e:2x2`` chip at the shapes ``chip_smoke.py``
serves (S jobs x K=1024 references x M=512 samples), and check that the
compiled program holds the Mosaic kernel (``tpu_custom_call``) and fits
one chip's 16 GB of HBM.  Nothing runs, so results are not checked here
(the interpret-mode equivalence suites pin them).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import dtw as _dtw
from repro.kernels.dtw import (score_bank_offline_kernel,
                               score_bank_offline_var_kernel,
                               stream_bank_extend_kernel)

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
    dispatches it: 0 channels (distance), 3 (scored), 4 (approx
    probability) and 6 (exact probability)."""
    i32 = jnp.int32
    state = (spec((s, M, K)),) if nch == 0 else \
        (spec((s, M, K)), spec((nch, s, M, K)))
    per_job = (spec((s,), i32),)                               # ns
    if nch:
        per_job += (spec((s,)), spec((s,)))                    # sx, sxx
    if nch > 3:
        per_job += (spec((s, 3)),)                             # vstats
    bank = (spec((M, K)), spec((K,), i32))
    chunk = (spec((s, C)),) if nch <= 3 else (spec((s, C)), spec((s, C)))
    tail = (spec((s,), i32), spec((s,), i32))                  # nvalid, qlens

    if nch == 0:
        def fn(rows, ns, bank_t, lengths, chunks, nvalid, qlens):
            new, ns2 = stream_bank_extend_kernel(
                rows.transpose(0, 2, 1), ns, bank_t.T, lengths, chunks,
                nvalid, qlens, band=band, interpret=False)
            return new.transpose(0, 2, 1), ns2
    else:
        entry = {3: _dtw.bank_extend_tick_scored_dispatch,
                 4: _dtw.bank_extend_tick_scored_var_approx_dispatch,
                 6: _dtw.bank_extend_tick_scored_var_dispatch}[nch]

        def fn(*args):
            return entry(*args, band=band, use_kernel=True, interpret=False)
    _check(fn, *state, *per_job, *bank, *chunk, *tail)


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
