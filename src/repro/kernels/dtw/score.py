"""Pallas TPU kernel: matrix-free offline bank scoring (the finish-path
whole-DB match).

One ``pallas_call`` renders the closed-end warp correlation of J complete
queries against the whole padded [K, M] reference bank — the offline
mirror of the fused streaming tick (``stream.py``).  The grid is
(query, reference-tile); each program runs its query through the full DP
with the [BK, M] row slice AND the three warp-path correlation-moment
slabs (sy, syy, sxy) pinned in VMEM scratch, then reduces to the [BK]
scores and endpoint distances **in-kernel** — the only HBM writes are the
[J, K] score/distance tiles, never a row, a moment slab, or a [K, N, M]
matrix.

Row updates and moment carries are the streaming scored kernel's
(``stream._stream_scored_kernel``): min-plus Hillis-Steele row scan,
backtrack-identical predecessor selection (diag, then vert, then horiz),
horizontal runs telescoped through one log2(M) anchored forward-fill.
The closed-end reduction reads row/moments at column ``lengths[k] - 1``
(the alignment endpoint D(N, M_k) of paper Eq. 1) instead of the
streaming open-end argmin, and evaluates ``core.dtw._corr_from_moments``
— the same score tail the jnp wavefront uses, so kernel == jnp is pinned
bit-identical on dyadic-grid data (tests/test_scored_matching.py) and
differs elsewhere only by warp-path tie flips.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .stream import (_INF, _MOM_SHIFT, _compiler_params, _pad_bank,
                     _per_job, _scored_row, _smem_job_spec)

__all__ = ["score_bank_offline_kernel", "score_bank_offline",
           "score_bank_offline_var_kernel",
           "score_bank_offline_var_approx_kernel"]


def _endpoint_moments(xlen, x_ref, vx_ref, len_ref, bank_ref, row_scr,
                      mom_scr, *, n: int, nch: int, band: Optional[int]):
    """Run one query's full DP against the [BK, M] tile with the row and
    its ``nch`` moment slabs in VMEM scratch, then read them at each
    reference's closed endpoint column ``len - 1`` -> (dist, [sel]), all
    [BK, 1] columns."""
    bank = bank_ref[...]                           # [BK, M]
    lens = len_ref[...]                            # [BK, 1]
    col = jax.lax.broadcasted_iota(jnp.int32, bank.shape, 1)
    yc = bank - _MOM_SHIFT
    yy = yc * yc
    row_scr[...] = jnp.full(bank.shape, _INF, jnp.float32)
    mom_scr[...] = jnp.zeros(mom_scr.shape, jnp.float32)

    def body(i, carry):
        @pl.when(i < xlen)
        def _():
            corner = jnp.where(i == 0, 0.0, _INF)
            new, moms = _scored_row(
                x_ref[0, 0, i], None if vx_ref is None else vx_ref[0, 0, i],
                row_scr[...], [mom_scr[ch] for ch in range(nch)], bank, yc,
                yy, col, corner, (lens, i, xlen), band)
            row_scr[...] = new
            for ch in range(nch):
                mom_scr[ch] = moms[ch]
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    onehot = col == lens - 1

    def pick(v):
        return jnp.sum(jnp.where(onehot, v, 0.0), axis=1, keepdims=True)

    return pick(row_scr[...]), [pick(mom_scr[ch]) for ch in range(nch)]


def _endpoint_kernel(xlen_ref, x_ref, *refs, n: int, nch: int,
                     band: Optional[int]):
    """One (query, reference-tile) program: full-query DP + ``nch``
    moment slabs in VMEM, then the closed-end reads -> one
    [1, 1 + nch, BK, 1] output block (endpoint distance, then the
    endpoint moments).  The score and probability tails run on these
    in the caller's jit, so they stay the single jnp definitions in
    ``core.dtw`` (``lax.erfc`` has no Mosaic lowering)."""
    if nch > 3:
        vx_ref, len_ref, bank_ref, out_ref, row_scr, mom_scr = refs
    else:
        vx_ref = None
        len_ref, bank_ref, out_ref, row_scr, mom_scr = refs
    dist, msel = _endpoint_moments(xlen_ref[0, 0, 0], x_ref, vx_ref,
                                   len_ref, bank_ref, row_scr, mom_scr,
                                   n=n, nch=nch, band=band)
    for ch, v in enumerate([dist] + msel):
        out_ref[0, ch] = v


def _offline_call(xs, xlens, bank, lengths, *, nch: int, band, block_k: int,
                  interpret: bool, xvars=None):
    """The (query, reference-tile) grid shared by the offline scorers:
    per-job scalars and queries in SMEM, the bank tile and its [BK, 1]
    lengths in VMEM -> (dists [J, K], endpoint moments [nch, J, K])."""
    j, n = xs.shape
    k, m = bank.shape
    queries = [xs] if xvars is None else [xs, xvars]
    kernel = functools.partial(_endpoint_kernel, n=n, nch=nch, band=band)
    out = pl.pallas_call(
        kernel,
        grid=(j, k // block_k),
        in_specs=[_smem_job_spec(1)] + [_smem_job_spec(n)] * len(queries)
        + [
            pl.BlockSpec((block_k, 1), lambda ji, ki: (ki, 0)),  # lengths
            pl.BlockSpec((block_k, m), lambda ji, ki: (ki, 0)),  # bank
        ],
        out_specs=pl.BlockSpec((1, 1 + nch, block_k, 1),
                               lambda ji, ki: (ji, 0, ki, 0)),
        out_shape=jax.ShapeDtypeStruct((j, 1 + nch, k, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_k, m), jnp.float32),
                        pltpu.VMEM((nch, block_k, m), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=f"dtw_score_offline_{nch}ch",
    )(_per_job(xlens), *map(_per_job, queries), lengths[:, None], bank)
    out = out[..., 0].transpose(1, 0, 2)                   # [1+nch, J, K]
    return out[0], out[1:]


@functools.partial(jax.jit,
                   static_argnames=("band", "block_k", "interpret"))
def _score_call(xs, xlens, bank, lengths, sx, sxx, band: Optional[int],
                block_k: int, interpret: bool):
    from ...core.dtw import _corr_from_moments

    dists, msel = _offline_call(xs, xlens, bank, lengths, nch=3, band=band,
                                block_k=block_k, interpret=interpret)
    nn = jnp.maximum(xlens, 1).astype(jnp.float32)[:, None]
    scores = _corr_from_moments(msel[0], msel[1], msel[2], sx[:, None],
                                sxx[:, None], nn)
    return jnp.where(xlens[:, None] > 0, scores, 0.0), dists


def score_bank_offline_kernel(xs, xlens, bank, lengths, sx, sxx,
                              band: Optional[int] = None,
                              block_k: int = 128,
                              interpret: bool = True):
    """Closed-end scores + endpoint distances of J queries vs the whole
    bank — one pallas_call.

    xs [J, N] f32 (padded; ``xlens`` [J] i32 true lengths); bank [K, M]
    f32 with lengths [K] i32; sx/sxx [J] f32 centered query folds
    (``core.dtw.query_moments``) -> (scores [J, K], dists [J, K]).  K is
    padded up to a ``block_k`` multiple and M to whole lane groups
    internally (padding never influences real cells; its outputs are
    sliced away).
    """
    xs = jnp.asarray(xs, jnp.float32)
    xlens = jnp.asarray(xlens, jnp.int32)
    sx = jnp.asarray(sx, jnp.float32)
    sxx = jnp.asarray(sxx, jnp.float32)
    k = bank.shape[0]
    bank, lengths, bk, _, _ = _pad_bank(jnp.asarray(bank, jnp.float32),
                                        jnp.asarray(lengths, jnp.int32),
                                        block_k)
    scores, dists = _score_call(xs, xlens, bank, lengths, sx, sxx, band,
                                bk, interpret)
    return scores[:, :k], dists[:, :k]


def score_bank_offline(xs, xlens, bank, lengths, sx, sxx,
                       band: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Backend-defaulted entry: compiled on TPU, interpret elsewhere."""
    from ..common import default_interpret
    interpret = default_interpret() if interpret is None else interpret
    return score_bank_offline_kernel(xs, xlens, bank, lengths, sx, sxx,
                                     band=band, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("band", "threshold", "block_k",
                                    "interpret", "approx"))
def _score_var_call(xs, xvars, xlens, bank, lengths, sx, sxx, vstats,
                    band: Optional[int], threshold: float, block_k: int,
                    interpret: bool, approx: bool = False):
    """Variance-carrying twin of :func:`_score_call`: six endpoint moment
    slabs (sy, syy, sxy, svy, svyy, svxy — each variance channel's delta
    is ``v_i *`` the matching base delta) feed the probabilistic tail
    (``core.dtw._prob_from_moments``, the single shared probability
    tail) beside the point score.

    ``approx=True`` is the calibration twin of the approx serving tick:
    FOUR slabs (only svy rides beside the base three) and the
    ``core.dtw._prob_from_moments_approx`` reduction — the offline
    oracle the approx tick's probabilities are pinned against."""
    from ...core.dtw import (_corr_from_moments, _prob_from_moments,
                             _prob_from_moments_approx)

    dists, msel = _offline_call(xs, xlens, bank, lengths,
                                nch=4 if approx else 6, band=band,
                                block_k=block_k, interpret=interpret,
                                xvars=xvars)
    nn = jnp.maximum(xlens, 1).astype(jnp.float32)[:, None]
    sx, sxx = sx[:, None], sxx[:, None]
    scores = _corr_from_moments(msel[0], msel[1], msel[2], sx, sxx, nn)
    tail = _prob_from_moments_approx if approx else _prob_from_moments
    probs = tail(*msel, sx, sxx, vstats[:, 0:1], vstats[:, 1:2],
                 vstats[:, 2:3], nn, jnp.float32(threshold))
    live = xlens[:, None] > 0
    return (jnp.where(live, scores, 0.0), jnp.where(live, probs, 0.0),
            dists)


def score_bank_offline_var_kernel(xs, xvars, xlens, bank, lengths, sx,
                                  sxx, vstats,
                                  band: Optional[int] = None,
                                  threshold: float = 0.9,
                                  block_k: int = 128,
                                  interpret: bool = True,
                                  approx: bool = False):
    """Closed-end scores + match probabilities + endpoint distances of J
    uncertain queries vs the whole bank — one pallas_call.

    As :func:`score_bank_offline_kernel` plus ``xvars`` [J, N] per-sample
    variances and ``vstats`` [J, 3] = (sv, svx, svxx) folds
    (``core.dtw.query_var_moments``) -> (scores, probs, dists) [J, K],
    with ``probs`` = P[true warp correlation >= ``threshold``].
    ``approx=True`` runs the four-slab single-proxy variant (see
    :func:`score_bank_offline_var_approx_kernel`).
    """
    xs = jnp.asarray(xs, jnp.float32)
    xvars = jnp.asarray(xvars, jnp.float32)
    xlens = jnp.asarray(xlens, jnp.int32)
    sx = jnp.asarray(sx, jnp.float32)
    sxx = jnp.asarray(sxx, jnp.float32)
    vstats = jnp.asarray(vstats, jnp.float32)
    k = bank.shape[0]
    bank, lengths, bk, _, _ = _pad_bank(jnp.asarray(bank, jnp.float32),
                                        jnp.asarray(lengths, jnp.int32),
                                        block_k)
    scores, probs, dists = _score_var_call(
        xs, xvars, xlens, bank, lengths, sx, sxx, vstats, band,
        float(threshold), bk, interpret, approx=approx)
    return scores[:, :k], probs[:, :k], dists[:, :k]


def score_bank_offline_var_approx_kernel(xs, xvars, xlens, bank, lengths,
                                         sx, sxx, vstats,
                                         band: Optional[int] = None,
                                         threshold: float = 0.9,
                                         block_k: int = 128,
                                         interpret: bool = True):
    """Approx-tail offline scorer: FOUR moment slabs (sy, syy, sxy, svy)
    and the ``core.dtw._prob_from_moments_approx`` reduction — the
    calibration harness's offline oracle for the approx serving tick
    (the verdict path keeps :func:`score_bank_offline_var_kernel`).
    Same signature and returns as the exact variant."""
    return score_bank_offline_var_kernel(
        xs, xvars, xlens, bank, lengths, sx, sxx, vstats, band=band,
        threshold=threshold, block_k=block_k, interpret=interpret,
        approx=True)
