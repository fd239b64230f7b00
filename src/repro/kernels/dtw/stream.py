"""Pallas TPU kernel: streaming bank-extend (the online-matching tick).

One service tick advances J in-flight streaming DPs (one per slot) by one
chunk of C samples against the whole padded [K, M] reference bank.  The
jnp reference (``core.dtw._bank_extend_many``) re-materializes a [J, K, M]
cost slab in HBM for every sample; here the grid is (job, reference-tile)
and each program keeps its [BK, M] DP row slice in VMEM across the entire
chunk — C row updates run back-to-back on-chip, with exactly one HBM read
and one HBM write of the row slice per tick.

Each row update is the same min-plus (tropical semiring) Hillis-Steele
scan as the offline wavefront kernel (``kernel.py``): the in-row
dependence D[i, j] = d[i, j] + min(m_j, D[i, j-1]) is an affine map in
(min, +), so a row solves in log2(M) shift+min steps on the VPU lanes.

Semantics mirror ``_bank_extend_many`` cell-for-cell (the tests pin this
on ragged banks, Sakoe-Chiba bands, and arbitrary chunkings):

* the virtual corner D[-1, -1] = 0 enters as the shifted-in value of a
  job's very first sample only (``ns == 0``);
* samples at or beyond ``nvalid[j]`` are padding and leave the row
  untouched (ragged per-job chunks);
* the banded variant re-derives each reference's Sakoe-Chiba center from
  its true length and the job's expected query length every row.

Two kernels share the row-update machinery:

* :func:`stream_bank_extend_kernel` — the distance-only tick (the
  large-K throughput mode): one [BK, M] DP row slice per program.
* :func:`stream_bank_extend_scored_kernel` — the FUSED scoring tick: the
  same program additionally pins the three warp-path correlation-moment
  slabs (sy, syy, sxy) of the DP row in VMEM and carries them through the DP
  with backtrack-identical predecessor selection (argmin over diag /
  vert / horiz with ``core.dtw.backtrack``'s tie order — diag first,
  then vert).  The horizontal moment recurrence m(i, j) = m(i, j-1) -
  pair(j-1) + pair(j) telescopes along a horizontal run to m(i, j) =
  base(j0) + pair(j), where j0 is the run's anchor (the nearest
  non-horiz cell at or left of j), so a row's moments solve in one
  log2(M) anchored forward-fill instead of a sequential column walk —
  the same depth as the min-plus distance scan.  Cell values and moments
  match ``core.dtw._bank_extend_diag_impl`` cell-for-cell (pinned by
  tests/test_kernels.py); the open-end score reduction stays outside the
  kernel (``core.dtw._scored_kernel_tick``, the kernel path of
  ``core.dtw.bank_extend_tick``, fuses it into the same jit).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["stream_bank_extend_kernel", "stream_bank_extend_scored_kernel"]

_INF = 3.0e38  # plain float: jnp scalars become captured consts in Pallas

#: Center for the correlation moments — MUST match ``core.dtw._MOM_SHIFT``
#: (the jnp twin) so the two scored paths accumulate identical slabs.
_MOM_SHIFT = 0.5

#: Lane width of a TPU vector register: the reference axis M rides the
#: lanes, so it is padded up to a multiple of this before the call.
_LANES = 128

#: Scoped VMEM a program may use (v5e holds 128 MiB per core).  The
#: full-M blocks of the six-channel slab, double-buffered, plus the row
#: update's temporaries need more than Mosaic's 16 MiB default.
_VMEM_LIMIT = 100 * 1024 * 1024


# -- in-kernel row-update machinery (shared with ``score.py``) --------------
#
# Every array below is a 2-D [BK, M] tile: references on sublanes, columns
# on lanes.  Per-job scalars come from SMEM, reference lengths arrive as a
# [BK, 1] column, and every lane shift is a rotate plus a mask, the forms
# Mosaic lowers natively.

def _shift(v, off: int, fill, col):
    """``v`` moved ``off`` columns right along its last axis, ``fill``
    entering at the left (``col`` is the column iota)."""
    return jnp.where(col >= off, pltpu.roll(v, off, v.ndim - 1), fill)


def _scan_steps(m_len: int) -> int:
    return int(np.ceil(np.log2(max(m_len, 2))))


def _minplus_scan2(a, s, col):
    """Row-batched twin of ``kernel._minplus_scan``: inclusive Hillis-
    Steele scan of the min-plus affine maps f_j(c) = min(c + a_j, s_j)
    along the last axis of [BK, M] tiles."""
    for t in range(_scan_steps(a.shape[-1])):
        off = 1 << t
        a_l = _shift(a, off, 0.0, col)
        s_l = _shift(s, off, _INF, col)
        s = jnp.minimum(s_l + a, s)
        a = a_l + a
    return s


def _fill_from_anchor(vals, anch, col):
    """Forward-fill each [BK, M] tile of ``vals`` from the nearest column
    at or left of it where ``anch`` (1.0/0.0) is set, in log2(M)
    Hillis-Steele steps.  Column 0 is always anchored in our use (a DP
    row's first cell can never pick the horizontal predecessor), so
    every column resolves."""
    for t in range(_scan_steps(anch.shape[-1])):
        off = 1 << t
        keep = anch > 0.0
        vals = [jnp.where(keep, v, _shift(v, off, 0.0, col)) for v in vals]
        anch = jnp.maximum(anch, _shift(anch, off, 0.0, col))
    return vals


def _band_cost(d, col, lens, pos, qlen, band: Optional[int]):
    """Mask the cost tile outside each reference's Sakoe-Chiba band:
    query row ``pos`` of an expected ``qlen`` centers on column
    pos * (len - 1) // (qlen - 1) of a reference of length ``len``."""
    if band is None:
        return d
    centers = (pos * (lens - 1)) // jnp.maximum(qlen - 1, 1)   # [BK, 1]
    return jnp.where(jnp.abs(col - centers) <= band, d, _INF)


def _distance_row(xi, row, bank, col, corner, centers_args, band):
    """One distance-only DP row update -> the new [BK, M] row."""
    d = _band_cost(jnp.abs(xi - bank), col, *centers_args, band)
    mn = jnp.minimum(row, _shift(row, 1, corner, col))
    new = _minplus_scan2(d, mn + d, col)
    if band is not None:
        new = jnp.where(d >= _INF, _INF, new)
    return new


def _scored_row(xi, vi, row, moms, bank, yc, yy, col, corner,
                centers_args, band):
    """One moment-carrying DP row update -> (new row, new moment tiles).

    ``moms`` is a list of nch [BK, M] tiles: sy, syy, sxy, then in
    variance mode ``vi *`` the first nch - 3 of them (6 = exact, 4 =
    approx).  Predecessor selection follows ``core.dtw.backtrack``'s tie
    order (diag, then vert, then horiz); horizontal runs telescope to
    base(anchor) + pair(j), solved by one anchored forward-fill.  Rows
    are clamped at ``_INF`` like the wavefront jnp twin, so ties in
    saturated regions resolve identically."""
    d = _band_cost(jnp.abs(xi - bank), col, *centers_args, band)
    p_diag = _shift(row, 1, corner, col)
    p_vert = row
    mn = jnp.minimum(p_vert, p_diag)
    new = _minplus_scan2(d, mn + d, col)
    if band is not None:
        new = jnp.where(d >= _INF, _INF, new)
    new = jnp.minimum(new, _INF)
    # the horizontal predecessor D[i, j-1] is the new row shifted right.
    p_horiz = _shift(new, 1, _INF, col)
    sel_diag = p_diag <= jnp.minimum(p_vert, p_horiz)
    sel_vert = jnp.logical_and(jnp.logical_not(sel_diag), p_vert <= p_horiz)
    anch = jnp.where(jnp.logical_or(sel_diag, sel_vert), 1.0, 0.0)
    # anchor cells read their predecessor's moments directly (the virtual
    # corner / first-sample boundary shifts in zeros); horizontal runs
    # fill from their anchor, then add this cell's aligned pair.
    base = [jnp.where(sel_diag, _shift(mc, 1, 0.0, col),
                      jnp.where(sel_vert, mc, 0.0)) for mc in moms]
    base = _fill_from_anchor(base, anch, col)
    xm = xi - _MOM_SHIFT
    dm = [yc, yy, xm * yc]
    dm += [vi * dm[ch] for ch in range(len(moms) - 3)]
    return new, [b + e for b, e in zip(base, dm)]


def _smem_job_spec(width: int):
    """One job's row of a [J, 1, width] per-job array, in SMEM (the unit
    axis keeps the block's last two dimensions whole, as the TPU
    lowering requires)."""
    return pl.BlockSpec((1, 1, width), lambda ji, ki: (ji, 0, 0),
                        memory_space=pltpu.SMEM)


def _per_job(a):
    """[J, w] (or [J]) per-job values -> the [J, 1, w] SMEM layout."""
    return a.reshape(a.shape[0], 1, -1)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)


# -- streaming tick kernels --------------------------------------------------

def _stream_kernel(sc_ref, x_ref, len_ref, rows_ref, bank_ref, out_ref, *,
                   c: int, band: Optional[int]):
    """One (job, reference-tile) program: advance the [BK, M] DP row slice
    by up to ``c`` samples, entirely in VMEM."""
    n0, nv, ql = sc_ref[0, 0, 0], sc_ref[0, 0, 1], sc_ref[0, 0, 2]
    bank = bank_ref[...]                           # [BK, M]
    col = jax.lax.broadcasted_iota(jnp.int32, bank.shape, 1)
    out_ref[0] = rows_ref[0]

    def body(i, carry):
        # samples past nvalid are chunk padding: the row stays put
        @pl.when(i < nv)
        def _():
            # virtual corner D[-1, -1] = 0 for the job's first sample only
            corner = jnp.where((n0 == 0) & (i == 0), 0.0, _INF)
            out_ref[0] = _distance_row(x_ref[0, 0, i], out_ref[0], bank, col,
                                       corner, (len_ref[...], n0 + i, ql),
                                       band)
        return carry

    jax.lax.fori_loop(0, c, body, 0)


def _stream_scored_kernel(sc_ref, x_ref, *refs, c: int, nch: int,
                          band: Optional[int], variance: bool = False):
    """One (job, reference-tile) program of the FUSED tick: advance the
    [BK, M] DP row slice AND its [nch, BK, M] warp-path moment slabs by
    up to ``c`` samples, entirely in VMEM.

    ``variance=True`` takes an extra per-sample variance ref right after
    the chunk ref: each variance channel's delta is ``v_i *`` the
    matching base channel's delta, so the identical anchored forward-
    fill carries them all (channels 0..2 arithmetic is untouched —
    bit-identity with the three-channel kernel and the jnp wavefront is
    preserved).  Exact mode twins all three base channels ([6, BK, M]:
    sy, syy, sxy, svy, svyy, svxy); approx mode twins only sy ([4, BK,
    M]: ..., svy — the serving tick's single σ²-proxy, see
    ``core.dtw._prob_from_moments_approx``).  The moments of saturated
    cells are don't-care (no finite path can descend from them) but
    stay finite."""
    if variance:
        (vx_ref, len_ref, rows_ref, moms_ref, bank_ref,
         out_ref, mout_ref) = refs
    else:
        len_ref, rows_ref, moms_ref, bank_ref, out_ref, mout_ref = refs
    n0, nv, ql = sc_ref[0, 0, 0], sc_ref[0, 0, 1], sc_ref[0, 0, 2]
    bank = bank_ref[...]                           # [BK, M]
    col = jax.lax.broadcasted_iota(jnp.int32, bank.shape, 1)
    yc = bank - _MOM_SHIFT                         # centered reference
    yy = yc * yc
    out_ref[0] = rows_ref[0]
    mout_ref[0] = moms_ref[0]

    def body(i, carry):
        @pl.when(i < nv)
        def _():
            corner = jnp.where((n0 == 0) & (i == 0), 0.0, _INF)
            new, moms = _scored_row(
                x_ref[0, 0, i], vx_ref[0, 0, i] if variance else None,
                out_ref[0], [mout_ref[0, ch] for ch in range(nch)], bank,
                yc, yy, col, corner, (len_ref[...], n0 + i, ql), band)
            out_ref[0] = new
            for ch in range(nch):
                mout_ref[0, ch] = moms[ch]
        return carry

    jax.lax.fori_loop(0, c, body, 0)


def _job_scalars(ns, nvalid, qlens):
    return _per_job(jnp.stack([ns, nvalid, qlens], axis=1))  # [J, 1, 3]


@functools.partial(jax.jit,
                   static_argnames=("band", "block_k", "interpret"))
def _stream_scored_call(rows, moms, ns, bank, lengths, chunks, nvalid,
                        qlens, band: Optional[int], block_k: int,
                        interpret: bool, vchunks=None):
    j, k, m = rows.shape
    c = chunks.shape[1]
    nch = moms.shape[1]                   # 3, or 4 / 6 in variance mode
    variance = vchunks is not None
    kernel = functools.partial(_stream_scored_kernel, c=c, nch=nch,
                               band=band, variance=variance)
    in_specs = [_smem_job_spec(3), _smem_job_spec(c)]      # scalars, chunk
    operands = [_job_scalars(ns, nvalid, qlens), _per_job(chunks)]
    if variance:
        in_specs.append(_smem_job_spec(c))                 # variances
        operands.append(_per_job(vchunks))
    in_specs += [
        pl.BlockSpec((block_k, 1), lambda ji, ki: (ki, 0)),  # lengths
        pl.BlockSpec((1, block_k, m),
                     lambda ji, ki: (ji, ki, 0)),          # rows
        pl.BlockSpec((1, nch, block_k, m),
                     lambda ji, ki: (ji, 0, ki, 0)),       # moments
        pl.BlockSpec((block_k, m), lambda ji, ki: (ki, 0)),  # bank
    ]
    operands += [lengths[:, None], rows, moms, bank]
    new_rows, new_moms = pl.pallas_call(
        kernel,
        grid=(j, k // block_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, m), lambda ji, ki: (ji, ki, 0)),
            pl.BlockSpec((1, nch, block_k, m),
                         lambda ji, ki: (ji, 0, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((j, k, m), jnp.float32),
            jax.ShapeDtypeStruct((j, nch, k, m), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="dtw_stream_scored",
    )(*operands)
    return new_rows, new_moms, ns + nvalid


@functools.partial(jax.jit,
                   static_argnames=("band", "block_k", "interpret"))
def _stream_call(rows, ns, bank, lengths, chunks, nvalid, qlens,
                 band: Optional[int], block_k: int, interpret: bool):
    j, k, m = rows.shape
    c = chunks.shape[1]
    kernel = functools.partial(_stream_kernel, c=c, band=band)
    new_rows = pl.pallas_call(
        kernel,
        grid=(j, k // block_k),
        in_specs=[
            _smem_job_spec(3),                                 # scalars
            _smem_job_spec(c),                                 # chunk
            pl.BlockSpec((block_k, 1), lambda ji, ki: (ki, 0)),  # lengths
            pl.BlockSpec((1, block_k, m),
                         lambda ji, ki: (ji, ki, 0)),          # rows
            pl.BlockSpec((block_k, m), lambda ji, ki: (ki, 0)),  # bank
        ],
        out_specs=pl.BlockSpec((1, block_k, m),
                               lambda ji, ki: (ji, ki, 0)),
        out_shape=jax.ShapeDtypeStruct((j, k, m), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="dtw_stream",
    )(_job_scalars(ns, nvalid, qlens), _per_job(chunks), lengths[:, None],
      rows, bank)
    return new_rows, ns + nvalid


def _pad_bank(bank, lengths, block_k: int):
    """Pad the bank to whole reference tiles and whole lane groups.

    Padding references (length 1, all-zero) never influence real ones —
    every cell update is per-reference — and padding columns sit right of
    every real column, which no DP cell to their left reads.  Returns
    ``(bank, lengths, bk, pad_k, pad_m)``."""
    k, m = bank.shape
    bk = min(block_k, k)
    pad_k = (-k) % bk
    pad_m = (-m) % _LANES
    bank = jnp.pad(bank, ((0, pad_k), (0, pad_m)))
    lengths = jnp.pad(lengths, (0, pad_k), constant_values=1)
    return bank, lengths, bk, pad_k, pad_m


def stream_bank_extend_kernel(rows, ns, bank, lengths, chunks, nvalid,
                              qlens, band: Optional[int] = None,
                              block_k: int = 128, interpret: bool = True):
    """Advance J streaming DPs by one padded chunk — one pallas_call.

    rows [J, K, M] f32; ns/nvalid/qlens [J] i32; bank [K, M] f32;
    lengths [K] i32; chunks [J, C] f32 -> (rows [J, K, M], ns [J]).
    The reference bank is tiled ``block_k`` rows per grid program; K is
    padded up internally when it does not divide evenly, and M up to a
    whole number of lane groups (see :func:`_pad_bank`).
    """
    rows = jnp.asarray(rows, jnp.float32)
    chunks = jnp.asarray(chunks, jnp.float32)
    ns = jnp.asarray(ns, jnp.int32)
    nvalid = jnp.asarray(nvalid, jnp.int32)
    qlens = jnp.asarray(qlens, jnp.int32)
    j, k, m = rows.shape
    bank, lengths, bk, pad_k, pad_m = _pad_bank(
        jnp.asarray(bank, jnp.float32), jnp.asarray(lengths, jnp.int32),
        block_k)
    if pad_k or pad_m:
        rows = jnp.pad(rows, ((0, 0), (0, pad_k), (0, pad_m)),
                       constant_values=_INF)
    new_rows, ns2 = _stream_call(rows, ns, bank, lengths, chunks, nvalid,
                                 qlens, band, bk, interpret)
    return new_rows[:, :k, :m], ns2


def stream_bank_extend_scored_kernel(rows, moms, ns, bank, lengths, chunks,
                                     nvalid, qlens,
                                     band: Optional[int] = None,
                                     block_k: int = 128,
                                     interpret: bool = True,
                                     vchunks=None):
    """Advance J streaming DPs AND their warp-path correlation moments by
    one padded chunk — one pallas_call.

    rows [J, K, M] f32; moms [3, J, K, M] f32 (sy, syy, sxy slabs of the
    current DP row's cells); other args as
    :func:`stream_bank_extend_kernel`.  Returns ``(rows, moms, ns)`` with
    the same layouts.  Variance mode: pass ``vchunks`` [J, C] per-sample
    variances with a SIX-channel ``moms`` [6, J, K, M] (sy, syy, sxy,
    svy, svyy, svxy) for the exact tail, or a FOUR-channel [4, J, K, M]
    (sy, syy, sxy, svy) for the approx serving tick — the extra slabs
    ride the same VMEM row-scan.  The open-end score reduction over
    the returned slabs lives in ``core.dtw`` (``_tick_tail``, run by
    ``bank_extend_tick``) so the moment semantics stay defined in
    exactly one place.
    """
    rows = jnp.asarray(rows, jnp.float32)
    moms = jnp.asarray(moms, jnp.float32)
    chunks = jnp.asarray(chunks, jnp.float32)
    ns = jnp.asarray(ns, jnp.int32)
    nvalid = jnp.asarray(nvalid, jnp.int32)
    qlens = jnp.asarray(qlens, jnp.int32)
    if vchunks is not None:
        vchunks = jnp.asarray(vchunks, jnp.float32)
        if moms.shape[0] not in (4, 6):
            raise ValueError("variance mode needs a six-channel (exact) "
                             "or four-channel (approx) moment slab, got "
                             f"{moms.shape[0]} channels")
    j, k, m = rows.shape
    bank, lengths, bk, pad_k, pad_m = _pad_bank(
        jnp.asarray(bank, jnp.float32), jnp.asarray(lengths, jnp.int32),
        block_k)
    if pad_k or pad_m:
        rows = jnp.pad(rows, ((0, 0), (0, pad_k), (0, pad_m)),
                       constant_values=_INF)
        moms = jnp.pad(moms, ((0, 0), (0, 0), (0, pad_k), (0, pad_m)))
    new_rows, new_moms, ns2 = _stream_scored_call(
        rows, moms.transpose(1, 0, 2, 3), ns, bank, lengths, chunks,
        nvalid, qlens, band, bk, interpret, vchunks=vchunks)
    return (new_rows[:, :k, :m],
            new_moms.transpose(1, 0, 2, 3)[:, :, :k, :m], ns2)
