"""Dynamic Time Warping (paper §3.1.2, Eq. 1-2).

The paper's recurrence::

    D(i, j) = d(x_i, y_j) + min(D(i, j-1), D(i-1, j), D(i-1, j-1))

with ``d`` the pointwise Euclidean distance between utilization samples.

Three implementations, all agreeing to float tolerance:

* :func:`dtw_matrix` — pure-jnp, row-by-row ``lax.scan`` where each row is
  solved with a **min-plus associative scan** (the in-row dependence
  ``D[i,j] = min(m_j + d_j, D[i,j-1] + d_j)`` is an affine map in the
  tropical semiring, hence associative).  Depth O(N log M) instead of
  O(N·M); this is the TPU-friendly formulation and the ops-path default.
* ``repro.kernels.dtw`` — Pallas wavefront kernel (anti-diagonal
  parallelism across VPU lanes), validated against :mod:`ref` oracles.
* a numpy O(N·M) double loop lives in ``repro/kernels/dtw/ref.py`` as the
  oracle.

Backtracking (to build the warped series Y' of Eq. 3) is data-dependent and
O(N+M); it runs in numpy on the returned matrix.

Batched bank API (matching-phase hot path)
------------------------------------------
The matching phase compares one query against *every* reference in the
database (paper Fig. 4-b), so the per-pair functions above would cost one
device dispatch per reference.  The ``*_bank`` / ``*_pairs`` functions
instead take all K references packed into one ``[K, M]`` array (padded to a
common length M, with an ``int32 [K]`` vector of true lengths) and solve
every DP in a single jit dispatch:

* :func:`dtw_distance_bank` — distances only; keeps one ``[K, M]`` DP row as
  the scan carry (no [K, N, M] matrix materialization) and reads each
  distance at the dynamic column ``lengths[k] - 1``.
* :func:`dtw_score_bank` / :func:`dtw_score_bank_many` /
  :func:`dtw_score_pairs` — **matrix-free offline scoring**: the Eq. 3
  warp correlation of complete queries, computed by carrying the
  warp-path correlation moments through the DP (backtrack-identical
  predecessor selection) and reading them at the closed alignment
  endpoint ``(N-1, lengths[k]-1)``.  One dispatch returns the final
  ``[K]`` / ``[J, K]`` / ``[P]`` scores — no matrix stack, no host
  backtracking; on TPU backends they route to the Pallas offline kernel
  (``kernels.dtw.score``).  This is the engine behind
  ``similarity.similarity_bank``, ``match_application`` and every
  ``TuningService`` finish verdict.
* :func:`dtw_matrix_bank` / :func:`dtw_matrix_pairs` — full matrices
  ``[K, N, M]`` for when the matrix itself is needed (``dtw_warp``
  consumers, ``similarity_bank(matrix_path=True)``'s reference scoring
  path).
* :class:`DtwBankState` / :func:`dtw_bank_init` / :func:`dtw_bank_extend` —
  the **streaming** engine: the DP state is carried across arriving query
  chunks (row-wise [K, M] carry), so an in-flight job can be matched while
  it executes; any chunking reproduces the one-shot solve exactly.
* :func:`bank_extend_tick` — the **device-resident service tick**
  (serve.tuning's hot path): the same streaming recurrence evaluated
  along anti-diagonals of the chunk block (no per-sample [J, K, M] cost
  slab, no log(M) in-row scan), K-last layout so the reference axis
  vectorizes and shards, fused with on-device open-end prefix scoring
  when given a moment slab (warp-path correlation moments carried
  through the DP, [J, K] scores out — no row stack ever leaves the
  device).  One entry point for every channel set (none: distance only;
  3: scored; 4 / 6: with variances, below); on TPU it routes to the
  Pallas streaming kernels (``kernels.dtw.stream``) through
  :func:`_scored_kernel_tick`, with the DP row AND the moment slabs
  pinned in VMEM across the whole chunk.

Uncertain-series matching (variance mode)
-----------------------------------------
Real traces carry per-sample measurement noise; the variance mode
(:func:`bank_extend_tick` with ``vchunks``/``vstats``,
``dtw_score_bank_many(xvars=...)`` and their Pallas twins) propagates a
per-sample variance ``v_i`` through the SAME warp path and emit a match
*probability* P[true warp correlation >= threshold] beside the point
score.  Slab layout: the moment slab doubles from three channels
(sy, syy, sxy) to SIX — (sy, syy, sxy, svy, svyy, svxy), where channel
3 + c's per-cell delta is exactly ``v_i * delta_c`` — and the
path-independent query folds grow a [·, 3] ``vstats`` = (sv, svx, svxx)
companion to sx/sxx.  The probability tail (:func:`_prob_from_moments`,
one definition shared by every path, exactly like
:func:`_corr_from_moments` for the point score) disattenuates the
observed correlation for noise-inflated query variance and applies
first-order (delta-method) error propagation; zero input variance
reduces BITWISE to the point rule, so variance mode is a strict
generalization.  The channel set is static (a slab's shape, an argument
given or None), so the three-channel tick traces none of it.

Two probability modes share that machinery:

* **exact** (six channels, above) — the verdict tail.  ``finish()``
  scoring and every offline probability goes through it; its numbers
  are the contract.
* **approx** (:func:`bank_extend_tick` with a FOUR-channel slab) —
  the serving tail.  Only ``svy = Σ v_i·y~_j(i)`` rides the
  warp path beside (sy, syy, sxy); the two dropped channels (svyy,
  svxy) are reconstructed at the score tail by
  :func:`_prob_from_moments_approx` from the carried proxy plus the
  path-independent folds, via the warp-path regression ``y~ ≈ α + β·x~``
  (see its docstring).  Slab traffic drops from 7 carried channels
  (cell + 6) to 5 (cell + 3 + 1) — ~1.3x the exact *scored* tick
  instead of ~2x — which is what makes probability-gated serving
  affordable at every tick (``serve.tuning prob_mode="approx"``).
  Zero input variance reduces BITWISE to the same point rule as the
  exact tail, and the approx probability computed from an exact
  six-channel slab's first four channels is bit-identical to the
  dedicated four-channel carry (channel 3 IS svy in both layouts).

Padding correctness: ``D[:, j]`` only ever depends on columns ``<= j`` and
rows ``<= i``, so values in the padded tail cannot reach ``D[n-1, len_k-1]``
— banks may be padded with anything; we pad with the series' edge value.
The banded variants re-derive the Sakoe-Chiba band per series from its
*true* length (dynamic ``lengths[k]``), so a banked banded solve is exactly
the scalar banded solve of the unpadded series.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "cost_matrix",
    "dtw_matrix",
    "dtw_distance",
    "dtw_matrix_banded",
    "dtw_matrix_bank",
    "dtw_matrix_pairs",
    "dtw_distance_bank",
    "dtw_score_bank",
    "dtw_score_bank_many",
    "dtw_score_pairs",
    "query_moments",
    "query_var_moments",
    "ScoreBankPlan",
    "build_score_plan",
    "DtwBankState",
    "dtw_bank_init",
    "dtw_bank_extend",
    "bank_extend_tick",
    "backtrack",
    "warp_to",
    "dtw_warp",
]

_INF = jnp.float32(3.0e38)

#: reference-tile width of the Pallas streaming tick kernels
#: (``kernels.dtw.stream``; the ``block_k`` of :func:`bank_extend_tick`).
TICK_BLOCK_K = 128


def _kernel_backend() -> bool:
    """Whether the dispatchers default to the Pallas kernels (a TPU
    backend) rather than the jnp twins."""
    return jax.default_backend() == "tpu"


def cost_matrix(x: jax.Array, y: jax.Array) -> jax.Array:
    """Pairwise |x_i - y_j| (paper Eq. 2) -> [N, M]."""
    return jnp.abs(x[:, None] - y[None, :]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# min-plus scan formulation
# ---------------------------------------------------------------------------

def _minplus_affine_scan(a: jax.Array, s: jax.Array) -> jax.Array:
    """Inclusive composition of min-plus affine maps f_j(c) = min(c + a_j,
    s_j) along the last axis, applied to the initial carry c_{-1} = +inf.

    The maps compose associatively: (f2 o f1)(c) = min(c + a1 + a2,
    min(s1 + a2, s2)).  Applying the prefix composition to +inf leaves only
    the s-part.
    """

    def combine(f1, f2):  # f1 applied first
        a1, s1 = f1
        a2, s2 = f2
        return a1 + a2, jnp.minimum(s1 + a2, s2)

    _, s_acc = jax.lax.associative_scan(combine, (a, s), axis=-1)
    return s_acc


def _minplus_row(prev_row: jax.Array, d_row: jax.Array) -> jax.Array:
    """Solve one DP row given the previous row.

    m_j   = min(D[i-1, j], D[i-1, j-1])
    D[i,j] = d[i,j] + min(m_j, D[i,j-1])
           = min(s_j, D[i,j-1] + a_j)   with s_j = m_j + d_j, a_j = d_j.
    """
    shifted = jnp.concatenate([jnp.full((1,), _INF, prev_row.dtype),
                               prev_row[:-1]])
    m = jnp.minimum(prev_row, shifted)
    return _minplus_affine_scan(d_row, m + d_row)


@jax.jit
def dtw_matrix(x: jax.Array, y: jax.Array) -> jax.Array:
    """Full accumulated-cost matrix D — [N, M] (paper Eq. 1)."""
    d = cost_matrix(x, y)

    # Row 0: D[0, j] = cumsum(d[0, :j+1])
    row0 = jnp.cumsum(d[0])

    def step(prev_row, d_row):
        row = _minplus_row(prev_row, d_row)
        return row, row

    _, rows = jax.lax.scan(step, row0, d[1:])
    return jnp.concatenate([row0[None, :], rows], axis=0)


@jax.jit
def dtw_distance(x: jax.Array, y: jax.Array) -> jax.Array:
    """Similarity distance D(N, M) between two series."""
    return dtw_matrix(x, y)[-1, -1]


# ---------------------------------------------------------------------------
# Sakoe-Chiba banded variant (beyond-paper: O(N*w) work)
# ---------------------------------------------------------------------------

def _lengths_or_full(lengths: Optional[jax.Array], k: int, m: int) -> jax.Array:
    """int32 [K] true-length vector; defaults to the full padded width."""
    return jnp.asarray(lengths, jnp.int32) if lengths is not None \
        else jnp.full((k,), m, jnp.int32)


def _band_center(i: jax.Array, qlen: jax.Array, rlen: jax.Array) -> jax.Array:
    """Sakoe-Chiba band center (reference-axis column) of query row(s) i
    for a (qlen, rlen) series pair — THE band geometry; every banded
    variant (scalar, bank, pairs, wavefront) must derive its mask from
    this so batched == scalar stays structural."""
    return (i * (rlen - 1)) // jnp.maximum(qlen - 1, 1)


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_matrix_banded(x: jax.Array, y: jax.Array, band: int) -> jax.Array:
    """DTW restricted to |i*M/N - j| <= band.  Returns full [N, M] matrix
    with +inf outside the band (so backtracking still works)."""
    return _masked_matrix(x, y, None, None, band)


# ---------------------------------------------------------------------------
# Batched bank / pairs API (matching-phase hot path; single jit dispatch)
# ---------------------------------------------------------------------------

def _band_mask(n: int, m: int, qlen: jax.Array, rlen: jax.Array,
               band: int) -> jax.Array:
    """Sakoe-Chiba mask [n, m] for a (qlen, rlen) series pair embedded in an
    [n, m] padded grid.  For j < rlen, i < qlen this is exactly the mask of
    the unpadded scalar solve; the padded region is don't-care."""
    ii = jnp.arange(n, dtype=jnp.int32)[:, None]
    jj = jnp.arange(m, dtype=jnp.int32)[None, :]
    return jnp.abs(jj - _band_center(ii, qlen, rlen)) <= band


def _masked_matrix(x: jax.Array, y: jax.Array, qlen: Optional[jax.Array],
                   rlen: Optional[jax.Array], band: Optional[int]) -> jax.Array:
    """Full [N, M] accumulated-cost matrix for one (possibly padded) pair.
    Unbanded padding needs no mask at all: D[i, j] depends only on cells
    (<=i, <=j), so the valid region is untouched by the padded tail."""
    d = cost_matrix(x, y)
    n, m = d.shape
    if band is not None:
        ql = jnp.int32(n) if qlen is None else qlen.astype(jnp.int32)
        rl = jnp.int32(m) if rlen is None else rlen.astype(jnp.int32)
        d = jnp.where(_band_mask(n, m, ql, rl, band), d, _INF)

    def step(prev_row, d_row):
        row = _minplus_row(prev_row, d_row)
        if band is not None:
            row = jnp.where(d_row >= _INF, _INF, row)
        return row, row

    row0 = jnp.where(d[0] >= _INF, _INF, jnp.cumsum(d[0])) if band is not None \
        else jnp.cumsum(d[0])
    _, rows = jax.lax.scan(step, row0, d[1:])
    return jnp.concatenate([row0[None, :], rows], axis=0)


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_matrix_bank(x: jax.Array, bank: jax.Array,
                    lengths: Optional[jax.Array] = None,
                    band: Optional[int] = None) -> jax.Array:
    """One query x [N] against a padded bank [K, M] -> D matrices [K, N, M].

    ``lengths`` (int32 [K], true series lengths) is only consulted by the
    banded variant (the band is re-derived per series from its true
    length); callers slice ``D[k, :, :lengths[k]]`` before backtracking.
    """
    x = jnp.asarray(x, jnp.float32)
    bank = jnp.asarray(bank, jnp.float32)
    if band is None:
        return jax.vmap(lambda y: _masked_matrix(x, y, None, None, None))(bank)
    ls = _lengths_or_full(lengths, bank.shape[0], bank.shape[1])
    return jax.vmap(
        lambda y, l: _masked_matrix(x, y, None, l, band))(bank, ls)


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_matrix_pairs(xs: jax.Array, ys: jax.Array,
                     xlens: Optional[jax.Array] = None,
                     ylens: Optional[jax.Array] = None,
                     band: Optional[int] = None) -> jax.Array:
    """Pairwise batched DTW: queries xs [P, N] vs references ys [P, M] ->
    D matrices [P, N, M], one jit dispatch for all P pairs (used to batch
    the whole of ``match_application`` — every (param set, app) pair at
    once, ragged on both sides)."""
    xs = jnp.asarray(xs, jnp.float32)
    ys = jnp.asarray(ys, jnp.float32)
    if band is None:
        return jax.vmap(
            lambda x, y: _masked_matrix(x, y, None, None, None))(xs, ys)
    p = xs.shape[0]
    ql = _lengths_or_full(xlens, p, xs.shape[1])
    rl = _lengths_or_full(ylens, p, ys.shape[1])
    return jax.vmap(
        lambda x, y, a, b: _masked_matrix(x, y, a, b, band))(xs, ys, ql, rl)


#: Out-of-range sentinel for the wavefront cost gather: large enough that
#: |x - _BIG| dominates any real path cost, small enough that a handful of
#: additions stay representable before saturating at f32 +inf (which the
#: min-reductions handle fine either way).
_BIG = jnp.float32(1.0e38)

#: lax.scan unroll factor for the wavefront distance scan; 2 measurably
#: beats 1 and 4 on CPU (less loop overhead vs. live-range pressure).
_WAVEFRONT_UNROLL = 2


@functools.partial(jax.jit, static_argnames=("band",))
def dtw_distance_bank(x: jax.Array, bank: jax.Array,
                      lengths: Optional[jax.Array] = None,
                      band: Optional[int] = None) -> jax.Array:
    """Distances D(N, len_k) of one query against the whole bank -> [K].

    Anti-diagonal wavefront formulation: cell (i, j) lives on diagonal
    t = i + j at slot i, so the recurrence

        c_t[i] = d(i, t-i) + min(c_{t-1}[i], c_{t-1}[i-1], c_{t-2}[i-1])

    is purely elementwise over a [K, N] diagonal slab — O(K·N·M) total
    work with **no** log(M) scan factor, N+M-1 scan steps total (vs K·N
    for a per-pair loop), and a [K, N] carry (never [K, N, M]).  The cost
    diagonal d(·, t-·) is one contiguous dynamic-slice of the reversed,
    sentinel-padded bank.  Each distance is D[N-1, len_k-1], i.e. slot
    N-1 of diagonal t = N + len_k - 2; padding beyond ``lengths[k]`` can
    never influence it (D[i, j] depends only on cells (<=i, <=j)).

    The banded variant masks each diagonal with the per-series
    Sakoe-Chiba corridor re-derived from true lengths, so it equals the
    scalar ``dtw_matrix_banded(x, y_k[:len_k], band)[-1, -1]`` loop.
    """
    x = jnp.asarray(x, jnp.float32)
    bank = jnp.asarray(bank, jnp.float32)
    k, m = bank.shape
    n = x.shape[0]
    ls = _lengths_or_full(lengths, k, m)

    # reversed bank, sentinel-padded so slot i of diagonal t reads
    # y[t - i] = yrp[:, (n + m - 1 - t) + i] (out-of-range j -> _BIG).
    yrp = jnp.concatenate([jnp.full((k, n), _BIG), bank[:, ::-1],
                           jnp.full((k, n), _BIG)], axis=1)
    ii = jnp.arange(n, dtype=jnp.int32)
    if band is not None:
        # Sakoe-Chiba center of row i for series k (true length ls[k]).
        centers = _band_center(ii[None, :], jnp.int32(n),
                               ls[:, None])                      # [K, N]

    def step(carry, t):
        prev, prev2 = carry                     # c_{t-1}, c_{t-2}: [K, N]
        yd = jax.lax.dynamic_slice(yrp, (0, n + m - 1 - t), (k, n))
        d = jnp.abs(x[None, :] - yd)
        if band is not None:
            jj = t - ii                          # column of slot i
            d = jnp.where(jnp.abs(jj[None, :] - centers) <= band, d, _INF)
        # virtual corner D[-1, -1] = 0 enters as the shifted-in value of
        # the diagonal predecessor on the t == 0 step only.
        corner = jnp.where(t == 0, jnp.float32(0.0), _INF)
        p_left = jnp.concatenate(
            [jnp.full((k, 1), _INF), prev[:, : n - 1]], axis=1)
        p_diag = jnp.concatenate(
            [jnp.full((k, 1), corner), prev2[:, : n - 1]], axis=1)
        c = d + jnp.minimum(jnp.minimum(prev, p_left), p_diag)
        return (c, prev), c[:, n - 1]

    init = (jnp.full((k, n), _INF), jnp.full((k, n), _INF))
    _, outs = jax.lax.scan(step, init,
                           jnp.arange(n + m - 1, dtype=jnp.int32),
                           unroll=_WAVEFRONT_UNROLL)
    # distance_k = slot n-1 of diagonal n - 1 + (len_k - 1)
    return jnp.take_along_axis(outs.T, (ls + (n - 2))[:, None],
                               axis=1)[:, 0]


# ---------------------------------------------------------------------------
# Streaming (prefix) bank DTW — the online matching engine
# ---------------------------------------------------------------------------
#
# The offline ``dtw_distance_bank`` wavefront needs the full query up front
# (its carry is indexed by query row).  The streaming engine instead carries
# the *row-wise* DP state: after consuming i query samples the state holds
# D[i-1, :] for every reference — a single [K, M] slab — and each new sample
# applies one ``_minplus_row`` update.  Any chunking of the query therefore
# reproduces the one-shot solve exactly: the DP recurrence is identical,
# only the dispatch boundaries move (tests/test_streaming.py pins this
# under random chunkings, ragged and banded).
#
# Row 0 rides on the same update via a virtual corner: D[-1, -1] = 0 enters
# as the shifted-in value of the first update only, turning it into the
# cumsum initialisation of ``dtw_matrix``.
#
# Everything is batched one level further for the serving layer: the jitted
# kernel takes J independent in-flight jobs stacked as [J, K, M] rows so a
# whole tick of a multi-job service is ONE device dispatch (invalid tail
# samples of ragged per-job chunks are masked out and leave the state
# untouched).

#: Chunks are padded up to the next power of two (>= _CHUNK_MIN) before
#: hitting the jitted kernel so arbitrary tick sizes reuse a handful of
#: compiled shapes.
_CHUNK_MIN = 8


def _chunk_bucket(c: int) -> int:
    return max(_CHUNK_MIN, 1 << (max(c, 1) - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("band", "collect_rows"))
def _bank_extend_many(rows: jax.Array, ns: jax.Array, bank: jax.Array,
                      lengths: jax.Array, chunks: jax.Array,
                      nvalid: jax.Array, qlens: jax.Array,
                      band: Optional[int], collect_rows: bool):
    """Advance J streaming DPs by one padded chunk each — one dispatch.

    rows    [J, K, M]  last DP row per job (init +inf)
    ns      [J] int32  query samples consumed per job
    chunks  [J, C]     new samples (tail beyond ``nvalid[j]`` is ignored)
    qlens   [J] int32  expected total query length (banded variant only;
                       the Sakoe-Chiba center of row i needs it)

    Returns (rows, ns, collected) where ``collected`` is the [C, J, K, M]
    stack of post-step rows (the D-matrix rows the scoring layer backtracks
    over) when ``collect_rows``, else None.
    """
    j, c = chunks.shape
    k, m = bank.shape
    jj = jnp.arange(m, dtype=jnp.int32)

    def step(carry, inp):
        rows, ns = carry
        x_s, s = inp                               # [J], scalar
        valid = s < nvalid                         # [J]
        d = jnp.abs(x_s[:, None, None] - bank[None, :, :])     # [J, K, M]
        if band is not None:
            centers = _band_center(ns[:, None], qlens[:, None],
                                   lengths[None, :])           # [J, K]
            d = jnp.where(
                jnp.abs(jj[None, None, :] - centers[:, :, None]) <= band,
                d, _INF)
        # virtual corner D[-1, -1] = 0 for each job's first sample only
        corner = jnp.where(ns == 0, jnp.float32(0.0), _INF)    # [J]
        shifted = jnp.concatenate(
            [jnp.broadcast_to(corner[:, None, None], (j, k, 1)),
             rows[:, :, :-1]], axis=2)
        mn = jnp.minimum(rows, shifted)
        new = _minplus_affine_scan(d, mn + d)
        if band is not None:
            new = jnp.where(d >= _INF, _INF, new)
        rows = jnp.where(valid[:, None, None], new, rows)
        ns = ns + valid.astype(jnp.int32)
        return (rows, ns), (rows if collect_rows else jnp.zeros((0,)))

    (rows, ns), collected = jax.lax.scan(
        step, (rows, ns), (chunks.T, jnp.arange(c, dtype=jnp.int32)))
    return rows, ns, (collected if collect_rows else None)


# ---------------------------------------------------------------------------
# Device-resident streaming tick: wavefront chunk-extend + fused prefix
# scoring (the serving-layer hot path; see serve/tuning.py)
# ---------------------------------------------------------------------------
#
# ``_bank_extend_many`` advances row-by-row: every query sample costs a full
# [J, K, M] cost slab plus a log(M) Hillis-Steele scan over the reference
# axis — fine as a reference formulation, but the slab traffic dominates a
# service tick.  ``_bank_extend_diag_impl`` instead sweeps the [C, M] chunk
# block along anti-diagonals (the ``dtw_distance_bank`` trick lifted to a
# *resumable* chunk): cell (i, j) lives on diagonal t = i + j at slot i, so
# each of the C + M - 1 steps is a purely elementwise update of a [J, K, C]
# diagonal — no in-row scan, no [J, K, M] intermediate at all, and the
# previous tick's DP row enters as the t-indexed boundary of the block.
# Ragged per-job chunks pass through by forcing the vertical predecessor for
# padded samples (the row above slides down unchanged, keeping column
# alignment for the final-row extraction at slot C - 1).
#
# The same sweep optionally fuses the scoring layer on-device.  The host
# scorer (``similarity.prefix_similarity_bank``) backtracks D and
# correlates the query against the warped reference — which forces the
# [C, S, K, M] row stack back to the host every tick.  Instead we carry the
# warp-path correlation moments *forward* through the DP: each cell picks
# the predecessor ``backtrack`` would pick (argmin over (diag, vert, horiz)
# with the same tie order), and updates running (sy, syy, sxy) moments of
# the aligned pairs along that path.  ``warp_to`` keeps one pair per query
# row (later columns overwrite), so the transitions are
#
#     diag/vert:  m(i, j) = m(pred) + pair(x_i, y_j)
#     horiz:      m(i, j) = m(i, j-1) - pair(x_i, y_{j-1}) + pair(x_i, y_j)
#
# and the moments at the open-end argmin of the final row reproduce the
# host backtrack + RunningMoments score — without ever materializing a row
# stack.  sx/sxx/n are path-independent (one pair per query row) and ride
# as [J] scalars.  Values are centered by ``_MOM_SHIFT`` before
# accumulation (correlation is shift-invariant; centering keeps the f32
# cancellation in cov = sxy - sx*sy/n benign for [0, 1] utilization data).
#
# Tick layout: the tick functions put K on the LAST axis (state [J, M, K],
# bank transposed to [M, K]) so every diagonal update vectorizes over the
# large reference axis instead of the small chunk axis — measured 1.5-3x
# on CPU over the K-major layout, and it makes sharding the bank a plain
# last-axis partition.  The offline/collect APIs (``DtwBankState``,
# ``_bank_extend_many``) keep their [K, M] layout; ``serve.tuning`` owns
# the transposed state.

#: Center for the on-device correlation moments (utilization series live
#: in [0, 1]; any constant shift leaves the correlation unchanged).
_MOM_SHIFT = jnp.float32(0.5)

#: Sentinel guard: reference values beyond this magnitude are padding from
#: the reversed-bank gather, not data — their moment contribution is
#: zeroed so f32 overflow can never poison a valid path's accumulators.
_Y_VALID = jnp.float32(1.0e30)


@functools.partial(jax.jit, static_argnames=("band", "threshold"))
def _bank_extend_diag_impl(rows, moms, ns, sx, sxx, vstats, bank_t, lengths,
                           chunks, vchunks, nvalid, qlens, *,
                           band: Optional[int], threshold: float):
    """Wavefront chunk-extend of J streaming bank DPs, fused with
    on-device open-end prefix scoring when a moment slab is given: the
    jnp twin of :func:`_scored_kernel_tick`, with the same arguments and
    results (see :func:`bank_extend_tick`).  Everything is elementwise
    per reference k, so sharding the K axis is exact.

    rows    [J, M, K]      last DP row per job (init +inf), K-last layout
    moms    [nch, J, M, K] warp-path (sy, syy, sxy) moments of ``rows``'s
                           cells (init 0), or None: distance only
    ns      [J] int32      query samples consumed per job
    sx, sxx [J] f32        centered query moment scalars
    bank_t  [M, K]         transposed reference bank
    chunks  [J, C]         new samples (tail beyond ``nvalid[j]`` ignored)
    qlens   [J] int32      total expected query length (banded only)

    Variance mode (``vchunks`` [J, C] per-sample measurement variances,
    ``vstats`` [J, 3] running (sv, svx, svxx) folds, ``threshold`` the
    static match threshold): ``moms`` doubles to SIX channels
    [6, J, M, K] — (sy, syy, sxy, svy, svyy, svxy), where each variance
    channel's per-cell delta is exactly ``v_i *`` the matching base
    channel's delta, so the identical anchored/telescoped transitions
    propagate them along the same backtrack-identical warp path.  A
    FOUR-channel ``moms`` [4, J, M, K] selects the approx tail instead:
    only the svy proxy rides the path (delta ``v_i * delta_sy``) and
    the probability reduction is :func:`_prob_from_moments_approx`.

    The score tail is :func:`_tick_tail`.  Cell values match
    ``_bank_extend_many`` to f32 tolerance (same recurrence, different
    evaluation order).
    """
    j, c = chunks.shape
    m, k = bank_t.shape
    ii = jnp.arange(c, dtype=jnp.int32)
    # reversed, sentinel-padded bank: slot i of diagonal t reads y[t - i]
    # (out-of-grid columns -> _BIG, which |x - .| turns into a huge cost).
    yrp = jnp.concatenate([jnp.full((c, k), _BIG), bank_t[::-1],
                           jnp.full((c, k), _BIG)], axis=0)        # [M+2C, K]
    # virtual corner D[-1, -1] = 0 for each job's very first sample.
    corner = jnp.where(ns == 0, jnp.float32(0.0), _INF)            # [J]
    # boundary row of the chunk block plus its moments, merged into ONE
    # diagonal-indexed array so each step needs a single dynamic slice:
    # index t is the diag predecessor D[-1, t-1], t + 1 the vert D[-1, t].
    prow = jnp.concatenate(
        [jnp.broadcast_to(corner[:, None, None], (j, 1, k)), rows,
         jnp.full((j, c, k), _INF)], axis=1)                       # [J,M+C+1,K]
    score = moms is not None
    nch = moms.shape[0] if score else 0       # 3, or 4 / 6 with variances
    if score:
        bpad = jnp.concatenate(
            [prow[None], jnp.concatenate(
                [jnp.zeros((nch, j, 1, k)), moms,
                 jnp.zeros((nch, j, c, k))], axis=2)], axis=0)  # [1+nch,J,.,K]
    else:
        bpad = prow[None]
    valid = ii[None, :] < nvalid[:, None]                          # [J, C]
    xm = chunks - _MOM_SHIFT                                       # [J, C]
    if band is not None:
        centers = _band_center((ns[:, None] + ii[None, :])[:, :, None],
                               qlens[:, None, None],
                               lengths[None, None, :])             # [J, C, K]

    def step(carry, t):
        # Diagonal-reuse carry: step t's diag predecessors and previous-
        # column deltas equal step t-1's vert predecessors and deltas
        # bit-for-bit (both splice bpad[..., t] ahead of the t-2
        # diagonal; delta(t-1) pairs x_i with y[t-1-i] exactly as
        # delta_prev(t) would), so they ride in the carry instead of
        # being re-gathered/re-multiplied every step — one slab copy per
        # moment channel per step instead of two, which is what keeps
        # the 6-channel variance slab's tick well under 2x the
        # 3-channel tick's cost.
        prev, pvert, mprev, mvert, dprev = carry    # [J,C,K] / [nch,J,C,K]
        # y diagonal: slot i of diagonal t reads y[t - i].
        yd = jax.lax.dynamic_slice(yrp, (c + m - 1 - t, 0), (c, k))
        d = jnp.abs(chunks[:, :, None] - yd[None])                 # [J,C,K]
        if band is not None:
            d = jnp.where(jnp.abs((t - ii)[None, :, None] - centers)
                          <= band, d, _INF)
        bsl = jax.lax.dynamic_slice(bpad, (0, 0, t + 1, 0),
                                    (bpad.shape[0], j, 1, k))
        p_vert = jnp.concatenate([bsl[0], prev[:, : c - 1]], axis=1)
        p_diag = pvert
        p_horiz = prev
        best = jnp.minimum(jnp.minimum(p_diag, p_vert), p_horiz)
        # clamp at _INF: keeps banded / out-of-grid cells finite (f32
        # would overflow to inf after a few accumulations otherwise).
        cell = jnp.minimum(d + best, _INF)
        # padded samples pass through vertically: the row above slides
        # down unchanged, so slot C-1 always carries the last VALID row.
        cell = jnp.where(valid[:, :, None], cell, p_vert)
        if not score:
            return (cell, p_vert, mprev, mvert, dprev), cell[:, c - 1]

        # -- fused warp-path moments ------------------------------------
        yc = jnp.where(jnp.abs(yd) < _Y_VALID, yd - _MOM_SHIFT, 0.0)
        ycb = jnp.broadcast_to(yc[None, None], (1, j, c, k))
        delta = jnp.concatenate(
            [ycb, ycb * ycb, xm[None, :, :, None] * ycb], axis=0)
        if vchunks is not None:
            # variance channels: v_i times the matching base channel,
            # so the same transitions carry them along the same path.
            # Exact mode (nch == 6) twins all three base channels;
            # approx mode (nch == 4) twins only sy — the svy proxy.
            delta = jnp.concatenate(
                [delta, vchunks[None, :, :, None] * delta[:nch - 3]],
                axis=0)
        m_vert = jnp.concatenate([bsl[1:], mprev[:, :, : c - 1]], axis=2)
        m_diag = mvert
        # predecessor choice mirrors backtrack()'s np.argmin tie order:
        # diag first, then vert, then horiz.
        sel_diag = p_diag <= jnp.minimum(p_vert, p_horiz)          # [J,C,K]
        sel_vert = jnp.logical_and(~sel_diag, p_vert <= p_horiz)
        m_base = jnp.where(sel_diag[None], m_diag,
                           jnp.where(sel_vert[None], m_vert,
                                     mprev - dprev))
        m_cell = jnp.where(valid[None, :, :, None], m_base + delta,
                           m_vert)
        return (cell, p_vert, m_cell, m_vert, delta), (cell[:, c - 1],
                                                       m_cell[:, :, c - 1])

    minit = jnp.zeros((nch, j, c, k)) if score else jnp.zeros((3, 1, 1, 1))
    # pvert's init is step 0's diag predecessor: the boundary column
    # bpad[..., 0] (the virtual corner / carried row) ahead of +inf;
    # dprev's init is delta(-1) == 0 (step 0's previous column is the
    # all-sentinel diagonal, whose masked deltas vanish).
    pvinit = jnp.concatenate([prow[:, 0:1], jnp.full((j, c - 1, k), _INF)],
                             axis=1)
    init = (jnp.full((j, c, k), _INF), pvinit, minit, minit, minit)
    _, outs = jax.lax.scan(step, init,
                           jnp.arange(c + m - 1, dtype=jnp.int32),
                           unroll=_WAVEFRONT_UNROLL)
    if score:
        row_outs, mom_outs = outs
    else:
        row_outs, mom_outs = outs, None
    # slot C-1 finishes column j = t - (C-1): steps C-1 .. C+M-2 emit the
    # post-chunk DP row (and its moments) column by column.
    new_rows = row_outs[c - 1:].transpose(1, 0, 2)                 # [J, M, K]
    if not score:
        return new_rows, None, ns + nvalid, sx, sxx, None
    new_moms = mom_outs[c - 1:].transpose(1, 2, 0, 3)            # [nch,J,M,K]
    return _tick_tail(new_rows, new_moms, ns, sx, sxx, vstats, lengths,
                      chunks, vchunks, nvalid, threshold)


def _corr_from_moments(sy, syy, sxy, sx, sxx, n):
    """``similarity.RunningMoments``'s correlation formula (and degenerate
    conventions) evaluated elementwise from broadcast-compatible moment
    arrays.  THE single definition of the on-device score tail: the fused
    streaming tick, the offline scorers and the Pallas offline kernel all
    call this, so device scores can only differ by the moments they feed
    in."""
    vx = jnp.maximum(sxx - sx * sx / n, 0.0)
    vy = jnp.maximum(syy - sy * sy / n, 0.0)
    cov = sxy - sx * sy / n
    denom = jnp.sqrt(vx * vy)
    corr = jnp.clip(cov / jnp.where(denom > 0, denom, 1.0), -1.0, 1.0)
    # Degeneracy is judged RELATIVE to the cancellation scale: a constant
    # f32 prefix does not yield vx == 0 but vx ~ eps * (sxx + sx^2/n)
    # (rounding garbage from the catastrophic cancellation), so an
    # absolute epsilon let garbage/garbage through as an arbitrary
    # clipped "correlation" that silently poisoned rankings.  Variance
    # within ~1e-5 of the cancellation scale is rounding noise, not
    # signal: the score is pinned to the degenerate conventions (1.0
    # for an identical constant pair, else 0.0).
    degx = vx <= 1e-5 * (sxx + sx * sx / n) + 1e-12
    degy = vy <= 1e-5 * (syy + sy * sy / n) + 1e-12
    both = degx & degy & (jnp.abs(sx - sy) / n < 1e-6)
    return jnp.where(degx | degy, jnp.where(both, 1.0, 0.0), corr)


def _open_end_moments(rows, moms, lengths):
    """The moment slabs read at each (job, reference)'s open end -> [nch,
    J, K]: the argmin of the DP row over the reference's true columns
    (the best reference *prefix*)."""
    m = rows.shape[1]
    colmask = jnp.arange(m, dtype=jnp.int32)[:, None] < lengths[None, :]
    masked = jnp.where(colmask[None], rows, _INF)
    j_end = jnp.argmin(masked, axis=1)                             # [J, K]
    return jnp.take_along_axis(moms, j_end[None, :, None, :],
                               axis=2)[:, :, 0, :]


def _moment_scores(rows, moms, ns, sx, sxx, lengths):
    """Open-end warp correlation per (job, reference) -> [J, K].

    The on-device tail of the fused scorer: read the warp-path moments at
    the open-end endpoint (:func:`_open_end_moments`) and evaluate the
    correlation with ``similarity.RunningMoments``'s formula and
    degenerate conventions.
    """
    msel = _open_end_moments(rows, moms, lengths)                 # [3, J, K]
    n = jnp.maximum(ns, 1).astype(jnp.float32)[:, None]            # [J, 1]
    out = _corr_from_moments(msel[0], msel[1], msel[2], sx[:, None],
                             sxx[:, None], n)
    # empty slots (no samples yet) follow RunningMoments' n == 0
    # convention — score 0, not the vacuous all-zero-moments 1.0.
    return jnp.where(ns[:, None] > 0, out, 0.0)


def _prob_from_moments(sy, syy, sxy, svy, svyy, svxy, sx, sxx,
                       sv, svx, svxx, n, threshold):
    """Match probability P[true warp correlation >= threshold] from the
    variance-carrying moment slabs — THE single probabilistic score tail
    (streaming tick, offline jnp scorer and the Pallas twins all call
    this, exactly like :func:`_corr_from_moments` for the point score).

    Model: each query sample x_i carries measurement variance v_i.  With
    the warp path held fixed (one aligned pair per query row, the
    ``warp_to`` convention), the observed correlation r is a smooth
    function of the moment sums, so first-order (delta-method) error
    propagation gives

        dr/dx_i  = a + 2 b x~_i + c y~_j(i)
        sigma_r^2 = a^2 sv + 4ab svx + 4b^2 svxx
                    + 2ac svy + 4bc svxy + c^2 svyy

    with a = dr/dsx, b = dr/dsxx, c = dr/dsxy = 1/sqrt(vx*vy) — every
    sum is one of the six path accumulators, carried through the DP by
    the same telescoping transitions as (sy, syy, sxy) (the variance
    channels are exactly ``v_i *`` the base channels).  Noise also
    BIASES r downward (it inflates vx while leaving cov unbiased), so r
    is disattenuated by sqrt(vx / (vx - sv)) — capped at 2x so a
    variance overestimate cannot manufacture a match — before the tail
    probability Phi((r^ - threshold) / sigma_r) is taken.

    Zero input variance makes every v-moment zero: the disattenuation
    factor is exactly 1.0 (vx/vx), sigma_r is exactly 0, and the result
    reduces BITWISE to the point rule ``r >= threshold`` (probability in
    {0.0, 1.0}), which is what pins probabilistic == point decisions on
    noise-free traces.
    """
    r = _corr_from_moments(sy, syy, sxy, sx, sxx, n)
    vx = jnp.maximum(sxx - sx * sx / n, 0.0)
    vy = jnp.maximum(syy - sy * sy / n, 0.0)
    denom = jnp.sqrt(vx * vy)
    safe_vx = jnp.where(vx > 0, vx, 1.0)
    # disattenuation: E[vx_obs] = vx_true + sv, cov unbiased.
    den = jnp.clip(vx - sv, vx * 0.25, vx)
    g = jnp.where(den > 0, jnp.sqrt(vx / jnp.where(den > 0, den, 1.0)),
                  1.0)
    r_hat = jnp.clip(r * g, -1.0, 1.0)
    c = 1.0 / jnp.where(denom > 0, denom, 1.0)
    a = -c * sy / n + r * sx / (n * safe_vx)
    b = -r / (2.0 * safe_vx)
    var_r = (a * a * sv + 4.0 * a * b * svx + 4.0 * b * b * svxx
             + 2.0 * a * c * svy + 4.0 * b * c * svxy + c * c * svyy)
    sigma = jnp.sqrt(jnp.maximum(var_r, 0.0))
    z = (r_hat - threshold) / jnp.where(sigma > 0, sigma, 1.0)
    phi = 0.5 * jax.lax.erfc(-z / jnp.sqrt(jnp.float32(2.0)))
    point = (r_hat >= threshold).astype(phi.dtype)
    return jnp.where(sigma > 0, phi, point)


def _prob_from_moments_approx(sy, syy, sxy, svy, sx, sxx, sv, svx, svxx,
                              n, threshold):
    """Approximate match probability from ONE carried variance channel —
    the serving-tick tail (:func:`_prob_from_moments` stays the verdict
    tail; THE single approx definition, shared by the jnp wavefront and
    both Pallas approx twins).

    Of the three path-dependent variance accumulators only
    ``svy = Σ v_i·y~_j(i)`` rides the warp path; the two dropped ones
    are reconstructed at the tail from the path-independent folds
    (sv, svx, svxx — note Σ v_i along the path IS sv: the warp keeps
    one pair per query row) via the warp-path regression
    ``y~_j(i) ≈ α + β·x~_i`` with β = cov/vx, α = (sy − β·sx)/n:

        svxy ≈ α·svx + β·svxx + (svx/sv)·resid
        svyy ≈ α²·sv + 2αβ·svx + β²·svxx
               + 2(α + β·svx/sv)·resid + sv·σ_ε²

    where ``resid = svy − (α·sv + β·svx)`` is the part of the carried
    proxy the regression line misses (it re-centers both
    reconstructions on the measured channel, so well-fit paths are
    reproduced almost exactly) and ``σ_ε² = max(vy − cov²/vx, 0)/n`` is
    the per-row regression residual variance.  Disattenuation, the
    delta-method variance algebra and every degenerate clamp are the
    exact tail's, with the reconstructed channels substituted.

    Zero input variance zeroes sv/svx/svxx/svy, hence resid, both
    reconstructions and every var_r term: sigma is exactly 0 and the
    result reduces BITWISE to the exact tail's point rule
    ``r^ >= threshold`` — approx and exact agree bit-for-bit on
    noise-free traces.  Constant queries/references ride the same
    safe-guards as the exact tail (safe_vx / sv_safe / clamped sqrt
    args), so the output is always finite, never NaN.
    """
    r = _corr_from_moments(sy, syy, sxy, sx, sxx, n)
    vx = jnp.maximum(sxx - sx * sx / n, 0.0)
    vy = jnp.maximum(syy - sy * sy / n, 0.0)
    cov = sxy - sx * sy / n
    denom = jnp.sqrt(vx * vy)
    safe_vx = jnp.where(vx > 0, vx, 1.0)
    den = jnp.clip(vx - sv, vx * 0.25, vx)
    g = jnp.where(den > 0, jnp.sqrt(vx / jnp.where(den > 0, den, 1.0)),
                  1.0)
    r_hat = jnp.clip(r * g, -1.0, 1.0)
    c = 1.0 / jnp.where(denom > 0, denom, 1.0)
    a = -c * sy / n + r * sx / (n * safe_vx)
    b = -r / (2.0 * safe_vx)
    # tail reconstruction of the dropped channels (see docstring)
    beta = cov / safe_vx
    alpha = (sy - beta * sx) / n
    sv_safe = jnp.where(sv > 0, sv, 1.0)
    resid = svy - (alpha * sv + beta * svx)
    svxy_hat = alpha * svx + beta * svxx + (svx / sv_safe) * resid
    sige2 = jnp.maximum(vy - cov * cov / safe_vx, 0.0) / n
    svyy_hat = jnp.maximum(
        alpha * alpha * sv + 2.0 * alpha * beta * svx
        + beta * beta * svxx
        + 2.0 * (alpha + beta * svx / sv_safe) * resid + sv * sige2,
        0.0)
    var_r = (a * a * sv + 4.0 * a * b * svx + 4.0 * b * b * svxx
             + 2.0 * a * c * svy + 4.0 * b * c * svxy_hat
             + c * c * svyy_hat)
    sigma = jnp.sqrt(jnp.maximum(var_r, 0.0))
    z = (r_hat - threshold) / jnp.where(sigma > 0, sigma, 1.0)
    phi = 0.5 * jax.lax.erfc(-z / jnp.sqrt(jnp.float32(2.0)))
    point = (r_hat >= threshold).astype(phi.dtype)
    return jnp.where(sigma > 0, phi, point)


def _moment_probs(rows, moms, ns, sx, sxx, vstats, lengths, threshold):
    """Open-end match probability per (job, reference) -> [J, K].

    The probabilistic twin of :func:`_moment_scores`: the same open-end
    endpoint, with the path-independent variance folds ``vstats`` = [J, 3]
    (sv, svx, svxx).  A six-channel slab (sy, syy, sxy, svy, svyy, svxy)
    takes the exact tail :func:`_prob_from_moments`, a four-channel one
    (sy, syy, sxy, svy) the approximate :func:`_prob_from_moments_approx`.
    The first four channels of an exact slab give the approximate tail
    bit-identical output (channel 3 is svy in both layouts), which is how
    the degraded approx tick of an exact-mode service reuses its slab.
    Empty slots get probability 0.0 (no evidence -> abstain).
    """
    msel = _open_end_moments(rows, moms, lengths)               # [nch, J, K]
    n = jnp.maximum(ns, 1).astype(jnp.float32)[:, None]            # [J, 1]
    tail = _prob_from_moments if moms.shape[0] == 6 \
        else _prob_from_moments_approx
    probs = tail(*msel, sx[:, None], sxx[:, None], vstats[:, 0][:, None],
                 vstats[:, 1][:, None], vstats[:, 2][:, None], n,
                 jnp.float32(threshold))
    return jnp.where(ns[:, None] > 0, probs, 0.0)


def _tick_tail(rows, moms, ns, sx, sxx, vstats, lengths, chunks, vchunks,
               nvalid, threshold):
    """The tick's epilogue after the DP, shared by the kernel path and the
    jnp twin: fold the chunk's valid samples into the path-independent
    query moments (sx, sxx and, with variances, ``vstats``) and score the
    open ends of the new ``rows`` — the point correlation, and with a
    variance slab the match probability (:func:`_moment_probs`).
    Returns the tick's 6-tuple, or its 8-tuple with variances."""
    c = chunks.shape[1]
    xm = chunks - _MOM_SHIFT
    vmask = (jnp.arange(c, dtype=jnp.int32)[None, :]
             < nvalid[:, None]).astype(jnp.float32)
    sx2 = sx + jnp.sum(xm * vmask, axis=1)
    sxx2 = sxx + jnp.sum(xm * xm * vmask, axis=1)
    ns2 = ns + nvalid
    if vchunks is None:
        scores = _moment_scores(rows, moms, ns2, sx2, sxx2, lengths)
        return rows, moms, ns2, sx2, sxx2, scores
    vq = vchunks * vmask
    vstats2 = vstats + jnp.stack(
        [jnp.sum(vq, axis=1), jnp.sum(vq * xm, axis=1),
         jnp.sum(vq * xm * xm, axis=1)], axis=1)                 # [J, 3]
    scores = _moment_scores(rows, moms[:3], ns2, sx2, sxx2, lengths)
    probs = _moment_probs(rows, moms, ns2, sx2, sxx2, vstats2, lengths,
                          threshold)
    return rows, moms, ns2, sx2, sxx2, scores, vstats2, probs


@functools.partial(jax.jit, static_argnames=("band", "threshold",
                                             "interpret", "block_k"))
def _scored_kernel_tick(rows, moms, ns, sx, sxx, vstats, bank_t, lengths,
                        chunks, vchunks, nvalid, qlens, band: Optional[int],
                        threshold: float, interpret: bool, block_k: int):
    """The Pallas tick in tick (K-last) layout: the layout shuffles into
    and out of the kernel's K-major convention, the pallas_call, the
    query folds and the score tail all trace into ONE jit, so nothing
    materializes between them beyond what XLA schedules.  Without a
    moment slab it is the distance-only kernel, and ``sx``/``sxx`` pass
    through."""
    from ..kernels.dtw import (stream_bank_extend_kernel,
                               stream_bank_extend_scored_kernel)
    if moms is None:
        rows_km, ns2 = stream_bank_extend_kernel(
            rows.transpose(0, 2, 1), ns, bank_t.T, lengths, chunks, nvalid,
            qlens, band=band, block_k=block_k, interpret=interpret)
        return rows_km.transpose(0, 2, 1), None, ns2, sx, sxx, None
    rows_km, moms_km, _ = stream_bank_extend_scored_kernel(
        rows.transpose(0, 2, 1), moms.transpose(0, 1, 3, 2), ns,
        bank_t.T, lengths, chunks, nvalid, qlens, band=band,
        block_k=block_k, interpret=interpret, vchunks=vchunks)
    return _tick_tail(rows_km.transpose(0, 2, 1),          # [J, M, K]
                      moms_km.transpose(0, 1, 3, 2),       # [nch, J, M, K]
                      ns, sx, sxx, vstats, lengths, chunks, vchunks,
                      nvalid, threshold)


def bank_extend_tick(rows, moms, ns, sx, sxx, vstats, bank_t, lengths,
                     chunks, vchunks, nvalid, qlens, *,
                     band: Optional[int] = None, threshold: float = 0.9,
                     use_kernel: Optional[bool] = None,
                     interpret: Optional[bool] = None,
                     block_k: int = TICK_BLOCK_K):
    """The streaming tick: advance J streaming DPs by one chunk and score
    them, in tick layout (rows [J, M, K], moms [nch, J, M, K], bank_t
    [M, K]).  The channel set comes from the arguments:

    * ``moms`` with 3 channels (sy, syy, sxy), ``vstats``/``vchunks``
      None: the point-rule scored tick -> ``(rows, moms, ns, sx, sxx,
      scores)``;
    * 6 channels with per-sample variances ``vchunks`` [J, C] and their
      folds ``vstats`` [J, 3]: the exact probability tail, 4 channels
      the approximate one -> ``(rows, moms, ns, sx, sxx, scores, vstats,
      probs)``;
    * ``moms`` None: the distance-only tick -> ``(rows, None, ns, sx,
      sxx, None)``, ``sx``/``sxx`` passed through.

    The tick runs the Pallas streaming kernel on TPU
    (:func:`_scored_kernel_tick`; DP row and moment slabs pinned in VMEM
    across the chunk) and the jnp wavefront :func:`_bank_extend_diag_impl`
    elsewhere, bit-identical on dyadic data.  ``use_kernel=False`` forces
    the jnp twin (the dispatch-resilience fallback); forcing the kernel
    on a CPU host runs it in Pallas interpret mode (``interpret``), which
    is how the equivalence suites pin kernel == jnp twin.
    """
    nch = 0 if moms is None else moms.shape[0]
    if nch not in ((0, 3) if vchunks is None else (4, 6)):
        given = "without" if vchunks is None else "with"
        raise ValueError(f"no tick takes {nch} moment channels {given} "
                         "variances (0 or 3 without, 4 or 6 with)")
    args = (rows, moms, ns, sx, sxx, vstats, bank_t, lengths, chunks,
            vchunks, nvalid, qlens)
    if use_kernel is None:
        use_kernel = _kernel_backend()
    if not use_kernel:
        return _bank_extend_diag_impl(*args, band=band, threshold=threshold)
    if interpret is None:
        from ..kernels.common import default_interpret
        interpret = default_interpret()
    return _scored_kernel_tick(*args, band=band, threshold=threshold,
                               interpret=interpret, block_k=block_k)


# ---------------------------------------------------------------------------
# Matrix-free offline scoring: closed-end moment-carrying bank / pairs
# scorers (the offline mirror of the fused streaming tick)
# ---------------------------------------------------------------------------
#
# ``similarity.similarity_bank`` historically materialized every [N, M]
# accumulated-cost matrix on device ([K, N, M] per dispatch), shipped the
# stack to the host and backtracked per reference in a Python loop.  The
# scorers below instead carry the warp-path correlation moments THROUGH the
# DP (the PR-4 streaming trick) and read them at the closed alignment
# endpoint ``(N-1, lengths[k]-1)`` — one dispatch returns the final [K]
# (or [J, K]) warp correlations directly, with no [K, N, M] materialization
# and no host backtrack.
#
# Formulation (column-indexed wavefront): slot j of the diagonal carry
# holds cell (i, j) with i = t - j, so the reference axis never moves —
# the bank (and every y-derived moment delta) is a static array pinned to
# the slots, and the per-step dynamic slice is only the tiny reversed-query
# window.  Predecessors: vert (i-1, j) = same slot, previous diagonal
# (UNSHIFTED); horiz (i, j-1) and diag (i-1, j-1) = slot j-1 of the
# previous / previous-previous diagonal (one shift each).  A slot stops
# updating once its query rows are exhausted (i >= xlen), so after the
# last step the carry IS the final DP row — nothing is emitted per step.
#
# Moments ride in BASE form: B(i, j) = m(i, j) - delta(i, j) (the cell's
# path moments excluding its own aligned pair).  Transitions become
#
#     diag/vert:  B(i, j) = B(pred) + delta(pred)
#     horiz:      B(i, j) = B(i, j-1)              (pure copy)
#
# — the horizontal telescoping of the streaming kernel with the subtract
# re-add replaced by a no-op; the final moments are reconstructed as
# B + delta(endpoint).  Both forms add the same pair values at the same
# path positions, so on dyadic-grid data they are bit-identical to the
# streaming wavefront / Pallas kernels (tests/test_scored_matching.py);
# on smooth data they agree to float tolerance and the usual caveat
# applies: near-tie argmin flips move individual warp paths, so scores
# match the host backtrack to ~1e-3, not ulps (same caveat as the fused
# streaming tick, see tests/test_kernels.py).
#
# The reference axis is tiled (``block_k``-wide, ascending-length-sorted
# with per-tile trimmed padding, pre-uploaded as a memoized
# ``ScoreBankPlan``) so the per-step working set stays cache-resident on
# CPU hosts and ragged banks pay for their own lengths — the same tiling
# the Pallas offline twin (``kernels.dtw.score``) gets from its
# (query, ref-tile) grid and VMEM pinning.  On TPU backends the public
# entry points route to that kernel.

#: Reference-tile width of the jnp offline scorer: slabs are
#: [4, block_k, M] f32, so 64 keeps the whole step working set around a
#: megabyte — L2-resident on CPU hosts (measured 2.5-3x over untiled).
_SCORE_BLOCK_K = 64

#: Job-group width of one jnp scorer dispatch: groups are dispatched
#: asynchronously so independent wavefronts overlap across host cores
#: (an in-program lax.map over the whole batch would serialize them);
#: within a group lax.map bounds the working set.
_SCORE_J_GROUP = 4


def _score_tile(x, xlen, bank_km, lengths, sx, sxx, band: Optional[int],
                unroll: int = _WAVEFRONT_UNROLL,
                steps: Optional[int] = None):
    """One query [N] vs one reference tile [BK, M] -> (scores, dists) [BK].

    Pure function of arrays (jit wrappers live below); ``x`` is the
    (possibly padded) query, ``xlen`` its true length — padded rows freeze
    the carry, so any padding reproduces the unpadded solve bitwise.
    ``steps`` truncates the wavefront (default n + m - 1): every cell is
    frozen once past its final DP row, so any ``steps`` covering the last
    live anti-diagonal — ``max(xlen) + max(lengths) - 1`` over the batch —
    reproduces the full sweep bitwise while skipping pure-freeze steps
    that query padding would otherwise pay for.
    """
    bk, m = bank_km.shape
    n = x.shape[0]
    jj = jnp.arange(m, dtype=jnp.int32)
    ts = jnp.arange(n + m - 1 if steps is None else min(steps, n + m - 1),
                    dtype=jnp.int32)
    # reversed query, sentinel-padded: the window starting at offset
    # m + n - 1 - t reads x[t - j] at position j (x[t-j-1] one further).
    xrp = jnp.concatenate([jnp.full((m,), _BIG), x[::-1],
                           jnp.full((m,), _BIG)])
    # Sakoe-Chiba mask for EVERY wavefront step, hoisted: the in-scan
    # center multiply/floordiv/compare chain costs as much as the DP
    # itself on CPU hosts, while the precomputed [T, BK, M] mask is one
    # boolean read per step (identical integer arithmetic, so scores are
    # bitwise unchanged).
    if band is not None:
        centers = _band_center(ts[:, None, None] - jj[None, None, :],
                               xlen, lengths[None, :, None])
        inband = jnp.abs(jj[None, None, :] - centers) <= band
    else:
        inband = jnp.zeros((ts.shape[0], 1, 1), jnp.bool_)
    # live-row window per step, hoisted for the same reason: slot j is
    # live at step t iff 0 <= t - j < xlen.
    ii = ts[:, None] - jj[None, :]
    lives = jnp.logical_and(ii >= 0, ii < xlen)          # [T, M]
    # centered bank + its shifted twin (the diag predecessor's y column)
    # and their squares: every y-derived moment delta, hoisted out of the
    # scan because slot j's reference value never changes.
    yc = bank_km - _MOM_SHIFT
    yc_sh = jnp.concatenate([jnp.zeros((bk, 1)), yc[:, :-1]], axis=1)
    yc2, yc_sh2 = yc * yc, yc_sh * yc_sh

    bcol = jnp.concatenate([jnp.full((1, bk, 1), _INF),
                            jnp.zeros((3, bk, 1))], axis=0)

    def step(carry, scanned):
        # P* pack [cell; sy; syy; sxy] as 4 channels; P1/P2 are the two
        # previous diagonals (frozen slots hold their final row).
        t, ok, live = scanned
        P1, P2 = carry                                       # [4, BK, M]
        xsl = jax.lax.dynamic_slice(xrp, (m + n - 1 - t,), (m + 1,))
        d = jnp.abs(xsl[:m][None, :] - bank_km)
        if band is not None:
            d = jnp.where(ok, d, _INF)
        P1s = jnp.concatenate([bcol, P1[:, :, :-1]], axis=2)
        # the virtual corner D[-1, -1] = 0 (empty-path moments) is the
        # shifted-in diag predecessor of cell (0, 0) on the t == 0 step.
        ccol = bcol.at[0].set(jnp.where(t == 0, 0.0, _INF))
        P2s = jnp.concatenate([ccol, P2[:, :, :-1]], axis=2)
        pd, pv, ph = P2s[0], P1[0], P1s[0]
        m1 = jnp.minimum(pv, ph)
        cell = jnp.minimum(d + jnp.minimum(pd, m1), _INF)
        # predecessor choice mirrors backtrack()'s np.argmin tie order
        # (diag, then vert, then horiz) — identical to the streaming
        # wavefront and the Pallas kernels.
        sd = pd <= m1
        anch = jnp.logical_or(sd, pv <= ph)
        # base-moment update: anchor cells read their predecessor's base
        # plus the predecessor's own pair delta; horizontal runs copy.
        # The predecessor row's x value is x[t-j-1] (sentinel windows
        # only feed don't-care cells: any finite path's predecessors are
        # in-grid, and the corner transition's y delta is zero because
        # yc_sh's first column is).
        xp = xsl[1:][None, :] - _MOM_SHIFT
        ysel = jnp.where(sd, yc_sh, yc)
        dpred = jnp.stack([ysel, jnp.where(sd, yc_sh2, yc2), xp * ysel])
        Bnew = jnp.where(anch[None],
                         jnp.where(sd[None], P2s[1:], P1[1:]) + dpred,
                         P1s[1:])
        Pnew = jnp.concatenate([cell[None], Bnew], axis=0)
        # slots freeze outside their live query rows: before row 0 they
        # keep the init boundary, after row xlen-1 the final DP row.
        Pnew = jnp.where(live[None, None, :], Pnew, P1)
        return (Pnew, P1), None

    init = jnp.concatenate([jnp.full((1, bk, m), _INF),
                            jnp.zeros((3, bk, m))], axis=0)
    (P1, _), _ = jax.lax.scan(step, (init, init), (ts, inband, lives),
                              unroll=unroll)
    jend = (lengths - 1).astype(jnp.int32)
    sel = jnp.take_along_axis(P1, jnp.broadcast_to(
        jend[None, :, None], (4, bk, 1)), axis=2)[:, :, 0]  # [4, BK]
    dist, Bf = sel[0], sel[1:]
    # reconstruct full moments: B + delta(endpoint) with the TRUE last
    # query sample (pass-through copies base moments untouched, so this
    # holds for padded queries too).
    yce = jnp.take_along_axis(bank_km, jend[:, None], axis=1)[:, 0] \
        - _MOM_SHIFT
    xme = jnp.take_along_axis(
        x, jnp.maximum(xlen - 1, 0)[None], axis=0)[0] - _MOM_SHIFT
    mf = Bf + jnp.stack([yce, yce * yce, xme * yce])
    nn = jnp.maximum(xlen, 1).astype(jnp.float32)
    scores = _corr_from_moments(mf[0], mf[1], mf[2], sx, sxx, nn)
    return jnp.where(xlen > 0, scores, 0.0), dist


@functools.partial(jax.jit, static_argnames=("band",))
def _score_tile_many(xs, xlens, bank_km, lengths, sx, sxx,
                     band: Optional[int]):
    """J queries x one reference tile -> (scores, dists) [J, BK].

    ``lax.map`` over jobs keeps the inner wavefront's [4, BK, M] working
    set cache-sized whatever J is; results are bitwise independent of J,
    of the tile split and of query padding (per-cell arithmetic never
    sees either).
    """

    def one_job(args):
        x, xlen, sxj, sxxj = args
        return _score_tile(x, xlen, bank_km, lengths, sxj, sxxj, band)

    return jax.lax.map(one_job, (xs, xlens, sx, sxx))


def _score_tile_var(x, xv, xlen, bank_km, lengths, sx, sxx, sv, svx, svxx,
                    band: Optional[int], threshold: float,
                    unroll: int = _WAVEFRONT_UNROLL,
                    approx: bool = False):
    """Variance-carrying twin of :func:`_score_tile`: one query [N] with
    per-sample variances ``xv`` [N] vs one reference tile [BK, M] ->
    (scores, probs, dists) [BK].

    The P pack grows to SEVEN channels [cell; sy; syy; sxy; svy; svyy;
    svxy]: each variance channel's predecessor delta is the matching base
    delta times the predecessor row's variance (the same BASE-form
    anchored/copy transitions carry all six), and the endpoint
    reconstruction adds ``v[xlen-1] *`` the base endpoint delta.  The
    variance window is ZERO-sentinel-padded (unlike the _BIG query
    sentinel): out-of-grid reads only feed don't-care cells, and zeros
    can never overflow a moment accumulator.

    ``approx=True`` switches the probability tail to
    :func:`_prob_from_moments_approx`, fed only (sy, syy, sxy, svy) —
    bit-identical to a dedicated four-channel carry (the svy channel's
    path arithmetic is unchanged), so this is the offline calibration
    reference for the approx serving tick without a second DP variant.
    """
    bk, m = bank_km.shape
    n = x.shape[0]
    jj = jnp.arange(m, dtype=jnp.int32)
    ts = jnp.arange(n + m - 1, dtype=jnp.int32)
    xrp = jnp.concatenate([jnp.full((m,), _BIG), x[::-1],
                           jnp.full((m,), _BIG)])
    vrp = jnp.concatenate([jnp.zeros((m,)), xv[::-1], jnp.zeros((m,))])
    if band is not None:
        centers = _band_center(ts[:, None, None] - jj[None, None, :],
                               xlen, lengths[None, :, None])
        inband = jnp.abs(jj[None, None, :] - centers) <= band
    else:
        inband = jnp.zeros((ts.shape[0], 1, 1), jnp.bool_)
    ii = ts[:, None] - jj[None, :]
    lives = jnp.logical_and(ii >= 0, ii < xlen)          # [T, M]
    yc = bank_km - _MOM_SHIFT
    yc_sh = jnp.concatenate([jnp.zeros((bk, 1)), yc[:, :-1]], axis=1)
    yc2, yc_sh2 = yc * yc, yc_sh * yc_sh

    bcol = jnp.concatenate([jnp.full((1, bk, 1), _INF),
                            jnp.zeros((6, bk, 1))], axis=0)

    def step(carry, scanned):
        t, ok, live = scanned
        P1, P2 = carry                                       # [7, BK, M]
        xsl = jax.lax.dynamic_slice(xrp, (m + n - 1 - t,), (m + 1,))
        vsl = jax.lax.dynamic_slice(vrp, (m + n - 1 - t,), (m + 1,))
        d = jnp.abs(xsl[:m][None, :] - bank_km)
        if band is not None:
            d = jnp.where(ok, d, _INF)
        P1s = jnp.concatenate([bcol, P1[:, :, :-1]], axis=2)
        ccol = bcol.at[0].set(jnp.where(t == 0, 0.0, _INF))
        P2s = jnp.concatenate([ccol, P2[:, :, :-1]], axis=2)
        pd, pv, ph = P2s[0], P1[0], P1s[0]
        m1 = jnp.minimum(pv, ph)
        cell = jnp.minimum(d + jnp.minimum(pd, m1), _INF)
        sd = pd <= m1
        anch = jnp.logical_or(sd, pv <= ph)
        xp = xsl[1:][None, :] - _MOM_SHIFT
        vp = vsl[1:][None, :]            # predecessor row's variance
        ysel = jnp.where(sd, yc_sh, yc)
        dpred3 = jnp.stack([ysel, jnp.where(sd, yc_sh2, yc2), xp * ysel])
        dpred = jnp.concatenate([dpred3, vp[None] * dpred3], axis=0)
        Bnew = jnp.where(anch[None],
                         jnp.where(sd[None], P2s[1:], P1[1:]) + dpred,
                         P1s[1:])
        Pnew = jnp.concatenate([cell[None], Bnew], axis=0)
        Pnew = jnp.where(live[None, None, :], Pnew, P1)
        return (Pnew, P1), None

    init = jnp.concatenate([jnp.full((1, bk, m), _INF),
                            jnp.zeros((6, bk, m))], axis=0)
    (P1, _), _ = jax.lax.scan(step, (init, init), (ts, inband, lives),
                              unroll=unroll)
    jend = (lengths - 1).astype(jnp.int32)
    sel = jnp.take_along_axis(P1, jnp.broadcast_to(
        jend[None, :, None], (7, bk, 1)), axis=2)[:, :, 0]  # [7, BK]
    dist, Bf = sel[0], sel[1:]
    yce = jnp.take_along_axis(bank_km, jend[:, None], axis=1)[:, 0] \
        - _MOM_SHIFT
    xme = jnp.take_along_axis(
        x, jnp.maximum(xlen - 1, 0)[None], axis=0)[0] - _MOM_SHIFT
    vme = jnp.take_along_axis(
        xv, jnp.maximum(xlen - 1, 0)[None], axis=0)[0]
    base_d = jnp.stack([yce, yce * yce, xme * yce])
    mf = Bf + jnp.concatenate([base_d, vme * base_d], axis=0)
    nn = jnp.maximum(xlen, 1).astype(jnp.float32)
    scores = _corr_from_moments(mf[0], mf[1], mf[2], sx, sxx, nn)
    if approx:
        probs = _prob_from_moments_approx(mf[0], mf[1], mf[2], mf[3],
                                          sx, sxx, sv, svx, svxx, nn,
                                          jnp.float32(threshold))
    else:
        probs = _prob_from_moments(mf[0], mf[1], mf[2], mf[3], mf[4],
                                   mf[5], sx, sxx, sv, svx, svxx, nn,
                                   jnp.float32(threshold))
    return (jnp.where(xlen > 0, scores, 0.0),
            jnp.where(xlen > 0, probs, 0.0), dist)


@functools.partial(jax.jit, static_argnames=("band", "threshold", "approx"))
def _score_tile_var_many(xs, xvs, xlens, bank_km, lengths, sx, sxx,
                         vstats, band: Optional[int], threshold: float,
                         approx: bool = False):
    """J queries (with variances) x one reference tile ->
    (scores, probs, dists) [J, BK]; the variance-mode column of
    :func:`_score_tile_many` (``lax.map`` over jobs, [7, BK, M] slabs).
    ``approx`` selects the single-proxy probability tail."""

    def one_job(args):
        x, xv, xlen, sxj, sxxj, vst = args
        return _score_tile_var(x, xv, xlen, bank_km, lengths, sxj, sxxj,
                               vst[0], vst[1], vst[2], band, threshold,
                               approx=approx)

    return jax.lax.map(one_job, (xs, xvs, xlens, sx, sxx, vstats))


#: Inner vmap width of one batched-verdict dispatch: wide enough to
#: amortize XLA's per-op loop overhead across jobs, narrow enough that
#: the [VW, 4, BK, M] per-op slab stays cache-resident on the small
#: banks the full-width verdict path serves (larger banks route to the
#: windowed wavefront instead).
_VERDICT_VMAP = 4


@functools.partial(jax.jit, static_argnames=("band", "steps"))
def _score_tile_verdict(xs, xlens, bank_km, lengths, sx, sxx,
                        band: Optional[int], steps: int):
    """J queries x one reference tile in ONE dispatch -> (scores, dists)
    [J, BK], the batched-verdict column of :func:`_score_tile_many`.

    ``lax.map`` over job groups of an inner ``vmap`` trades
    :func:`_score_tile_many`'s per-job op dispatches (the sequential-J
    cost on CPU hosts) for ``_VERDICT_VMAP``-wide slabs, and ``steps``
    (host-derived from the TRUE query lengths, bucketed so repeat drains
    reuse jit shapes) skips the pure-freeze tail that pow2 query padding
    appends.  Bitwise equal to per-job :func:`_score_tile` whatever J,
    the grouping, or the padding."""
    j = xs.shape[0]
    g = math.gcd(j, _VERDICT_VMAP)

    def one_job(x, xlen, sxj, sxxj):
        return _score_tile(x, xlen, bank_km, lengths, sxj, sxxj, band,
                           steps=steps)

    def one_group(args):
        return jax.vmap(one_job)(*args)

    ng = j // g
    scores, dists = jax.lax.map(one_group, (
        xs.reshape(ng, g, -1), xlens.reshape(ng, g),
        sx.reshape(ng, g), sxx.reshape(ng, g)))
    return scores.reshape(j, -1), dists.reshape(j, -1)


def _window_offset(t, xlen, min_len, band: int):
    """Leftmost column the banded wavefront can reach at step ``t``
    (minus one slack column), in exact int32 arithmetic.

    In-band cells of step t satisfy ``j >= (t*R - (band+1)*q)/(q + R)``
    with ``q = xlen-1`` and ``R = len_k-1`` (from inverting
    :func:`_band_center`'s floor); the bound is increasing in R, so the
    shortest reference in the tile gives the tile-wide minimum.  Every
    column strictly left of the returned offset is out-of-band for EVERY
    reference, which is what lets the windowed wavefront represent them
    as frozen (+inf, 0-moment) cells without computing them.
    """
    q = jnp.maximum(xlen - 1, 1).astype(jnp.int32)
    r = jnp.maximum(min_len - 1, 1).astype(jnp.int32)
    return (t * r - (band + 1) * q) // (q + r) - 1


def _window_width(xlens, lengths, m: int, band: int) -> int:
    """Static window width covering the band of every (query, tile
    reference) pair at every wavefront step, host-side exact integer
    arithmetic mirroring :func:`_window_offset`; padded to a multiple of
    16 so repeat verdicts reuse jit shapes."""
    xl = np.maximum(np.asarray(xlens, np.int64), 2)
    lengths = np.asarray(lengths, np.int64)
    q_lo, q_hi = int(xl.min()) - 1, int(xl.max()) - 1
    r_lo = max(int(lengths.min()) - 1, 1)
    r_hi = max(int(lengths.max()) - 1, 1)
    # exact sweep over every wavefront step: the kernel's SHARED left
    # offset uses (q_hi, r_lo); the right band edge is maximized over the
    # (q, r) corners (the bound is monotone in each variable separately,
    # so corner evaluation is exact).
    t = np.arange(q_hi + m - 1, dtype=np.int64)
    # offsets FREEZE for _VERDICT_SUPER consecutive steps (static
    # sub-step slicing in the kernel), so each step is covered by the
    # offset of its super-step start
    ts = (t // _VERDICT_SUPER) * _VERDICT_SUPER
    o = (ts * r_lo - (band + 1) * q_hi) // (q_hi + r_lo) - 1
    hi = np.full_like(t, -1)
    for q in (q_lo, q_hi):
        for r in (r_lo, r_hi):
            hi = np.maximum(hi, (t * r + band * q) // (q + r) + 1)
    w = int((np.minimum(hi, m - 1) - np.maximum(o, 0)).max()) + 4
    return min(m, -(-w // 16) * 16)


_VERDICT_GROUP = 8
#: wavefront steps per frozen-offset super-step in the windowed scorer
_VERDICT_SUPER = 4


@functools.partial(jax.jit, static_argnames=("band", "w", "group"))
def _score_tile_banded_many(xs, xlens, bank_km, lengths, sx, sxx,
                            band: int, w: int,
                            group: int = _VERDICT_GROUP):
    """Windowed twin of :func:`_score_tile_many` for banded verdicts:
    the scan carries only a ``w``-wide sliding window of each
    anti-diagonal instead of the full [BK, M] slab, so a banded verdict
    does O((N+M)*w) work instead of O((N+M)*M) — and the window offset
    is SHARED across the batch (derived from the batch's longest query),
    so the whole batch runs as one scan over [J, 4, BK, w'] slabs whose
    slices are plain scalar-offset copies.  A J=1 dispatch is dominated
    by per-step op overhead at these slab sizes; batching amortizes that
    overhead across jobs, which is what makes ``finish_many`` beat
    sequential finishes on a one-core host.

    Exactness: the window provably covers every in-band cell of every
    job (:func:`_window_offset` with the batch-max query length lower-
    bounds each job's own left band edge), in-window cells run the
    identical per-cell arithmetic (including the :func:`_band_center`
    mask), and everything outside the window is out-of-band for every
    (job, reference) — a (+inf, 0-moment) cell, which is exactly what
    the edge padding supplies.  The final query row's cell leaves the
    window one column per step, so it is emitted as scan output and the
    per-(job, reference) endpoints are gathered afterwards.  Scores and
    distances are bitwise identical to the full-width tile for any
    sufficient window, hence independent of batch composition.
    """
    jall, n = xs.shape
    bk, m = bank_km.shape
    u_sup = _VERDICT_SUPER
    # stored/computed span per SUPER-step: columns [o-2, o+w+2); the
    # offset freezes for u_sup consecutive wavefront steps so every
    # intra-super-step predecessor read is a STATIC slice (XLA fuses the
    # whole unrolled chain); one dynamic realignment per super-step.
    ws = w + 4
    g = math.gcd(jall, group)
    j = g
    yc_full = bank_km - _MOM_SHIFT
    # left-padded twins so the shifted (diag-predecessor) column is a
    # plain re-slice; column -1's yc_sh is 0 as in the full-width tile.
    # extra columns of back-fill keep every dynamic_slice in range
    # (reads there only feed out-of-band cells).
    ycp = jnp.concatenate([jnp.zeros((bk, 3)), yc_full,
                           jnp.zeros((bk, 2))], axis=1)
    ybp = jnp.concatenate([jnp.zeros((bk, 2)), bank_km,
                           jnp.zeros((bk, 2))], axis=1)
    r_min = jnp.maximum(jnp.min(lengths) - 1, 1).astype(jnp.int32)
    jend = (lengths - 1).astype(jnp.int32)
    n_steps = n + m - 1
    n_sup = -(-n_steps // u_sup)

    # frozen out-of-window cell: +inf distance, zero moments
    def blank(width):
        return jnp.concatenate(
            [jnp.full((j, 1, bk, width), _INF),
             jnp.zeros((j, 3, bk, width))], axis=1)

    edge1 = blank(1)
    edgeu = blank(u_sup + 2)

    def one_group(xs, xlens, sx, sxx):
        xrp = jnp.concatenate(
            [jnp.full((j, m + 2), _BIG), xs[:, ::-1],
             jnp.full((j, m + 2), _BIG)], axis=1)
        q_max = jnp.maximum(jnp.max(xlens) - 1, 1)

        def offset(t):
            return jnp.clip(
                (t * r_min - (band + 1) * q_max) // (q_max + r_min) - 1,
                0, max(m - w, 0))

        def super_step(carry, t0):
            P1, P2, o_prev = carry
            o = offset(t0)
            jj = o - 2 + jnp.arange(ws, dtype=jnp.int32)     # [ws] abs
            # realign both carries to the new span in ONE dynamic slice
            # each (the right edge-padding stands in for columns that
            # are out-of-band at every step it can be read for)
            sh = jnp.clip(o - o_prev, 0, u_sup + 1)
            P1 = jax.lax.dynamic_slice(
                jnp.concatenate([P1, edgeu], axis=3),
                (0, 0, 0, sh), (j, 4, bk, ws))
            P2 = jax.lax.dynamic_slice(
                jnp.concatenate([P2, edgeu], axis=3),
                (0, 0, 0, sh), (j, 4, bk, ws))
            # query / bank slabs for the whole super-step (o is frozen,
            # so sub-steps take static sub-slices)
            xbig = jax.lax.dynamic_slice(
                xrp, (0, m + n - 1 - (t0 + u_sup - 1) + o),
                (j, ws + u_sup))
            ysl = jax.lax.dynamic_slice(ycp, (0, o), (bk, ws + 1))
            yc, yc_sh = ysl[:, 1:], ysl[:, :-1]              # [BK, ws]
            yraw = jax.lax.dynamic_slice(ybp, (0, o), (bk, ws))
            emits = []
            for u in range(u_sup):
                t = t0 + u
                xsl = xbig[:, u_sup - 1 - u: u_sup - u + ws]  # [J, ws+1]
                d = jnp.abs(xsl[:, None, :ws] - yraw[None])   # [J,BK,ws]
                ii = t - jj                                   # [ws] rows
                centers = _band_center(ii[None, None, :],
                                       xlens[:, None, None],
                                       lengths[None, :, None])
                ok = jnp.logical_and(
                    jnp.abs(jj[None, None, :] - centers) <= band,
                    jnp.logical_and(jj >= 0, jj < m)[None, None, :])
                d = jnp.where(ok, d, _INF)
                # static shift-by-one: horiz/diag predecessors
                P1s = jnp.concatenate([edge1, P1[..., :-1]], axis=3)
                P2s = jnp.concatenate([edge1, P2[..., :-1]], axis=3)
                pd, pv, ph = P2s[:, 0], P1[:, 0], P1s[:, 0]
                # virtual corner D[-1,-1] = 0: diag predecessor of
                # column 0 on the t == 0 step
                pd = jnp.where(
                    jnp.logical_and(t == 0, jj == 0)[None, None, :],
                    0.0, pd)
                m1 = jnp.minimum(pv, ph)
                cell = jnp.minimum(d + jnp.minimum(pd, m1), _INF)
                sd = pd <= m1
                anch = jnp.logical_or(sd, pv <= ph)
                xp = xsl[:, None, 1:] - _MOM_SHIFT            # [J, 1, ws]
                ysel = jnp.where(sd, yc_sh[None], yc[None])
                dpred = jnp.stack(
                    [ysel, jnp.where(sd, (yc_sh * yc_sh)[None],
                                     (yc * yc)[None]), xp * ysel],
                    axis=1)
                Bnew = jnp.where(anch[:, None],
                                 jnp.where(sd[:, None], P2s[:, 1:],
                                           P1[:, 1:]) + dpred,
                                 P1s[:, 1:])
                Pnew = jnp.concatenate([cell[:, None], Bnew], axis=1)
                live = jnp.logical_and(ii[None, :] >= 0,
                                       ii[None, :] < xlens[:, None])
                Pnew = jnp.where(live[:, None, None, :], Pnew, P1)
                # final query row's cell: column t - (xlen_j - 1), per
                # job, captured the step it is computed
                eidx = jnp.clip(t - (xlens - 1) - (o - 2), 0, ws - 1)
                emits.append(jnp.take_along_axis(
                    Pnew, eidx[:, None, None, None], axis=3)[..., 0])
                P2, P1 = P1, Pnew
            return (P1, P2, o), jnp.stack(emits)  # [U, J, 4, BK]

        init = blank(ws)
        t0s = jnp.arange(n_sup, dtype=jnp.int32) * u_sup
        (_, _, _), ys = jax.lax.scan(
            super_step, (init, init, jnp.int32(0)), t0s)
        ys = ys.reshape(n_sup * u_sup, j, 4, bk)
        # ref k's closed-end endpoint was emitted at step
        # xlen_j - 1 + jend_k (always a true, non-overhang step)
        eidx = jnp.broadcast_to(
            (xlens[:, None] - 1 + jend[None, :])[:, None, :],
            (j, 4, bk))[None]
        sel = jnp.take_along_axis(ys, eidx, axis=0)[0]        # [J, 4, BK]
        dist, Bf = sel[:, 0], sel[:, 1:]                      # [J, BK]
        yce = jnp.take_along_axis(bank_km, jend[:, None], axis=1)[:, 0] \
            - _MOM_SHIFT                                      # [BK]
        xme = jnp.take_along_axis(
            xs, jnp.maximum(xlens - 1, 0)[:, None], axis=1)[:, 0] \
            - _MOM_SHIFT                                      # [J]
        mf = Bf + jnp.stack([jnp.broadcast_to(yce[None], (j, bk)),
                             jnp.broadcast_to((yce * yce)[None], (j, bk)),
                             xme[:, None] * yce[None]], axis=1)
        nn = jnp.maximum(xlens, 1).astype(jnp.float32)[:, None]
        scores = _corr_from_moments(mf[:, 0], mf[:, 1], mf[:, 2],
                                    sx[:, None], sxx[:, None], nn)
        return jnp.where(xlens[:, None] > 0, scores, 0.0), dist

    xlens = xlens.astype(jnp.int32)
    if g == jall:
        return one_group(xs, xlens, sx, sxx)
    ng = jall // g
    scores, dist = jax.lax.map(
        lambda a: one_group(*a),
        (xs.reshape(ng, g, n), xlens.reshape(ng, g),
         sx.reshape(ng, g), sxx.reshape(ng, g)))
    return scores.reshape(jall, bk), dist.reshape(jall, bk)



@functools.partial(jax.jit, static_argnames=("band",))
def _score_pairs_impl(xs, ys, xlens, ylens, sx, sxx,
                      band: Optional[int]):
    """P ragged (query, reference) pairs -> (scores, dists) [P]; one
    dispatch (vmapped single-pair tiles — [4, P, M] slabs stay small)."""

    def one(x, y, xlen, ylen, sxp, sxxp):
        sc, di = _score_tile(x, xlen, y[None, :], ylen[None], sxp, sxxp,
                             band)
        return sc[0], di[0]

    return jax.vmap(one)(xs, ys, xlens, ylens, sx, sxx)


def query_moments(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side centered query folds (sx, sxx) for the closed-end
    scorers, accumulated in float64 from the UNPADDED samples — the same
    job always contributes bit-identical folds however its verdict is
    batched, which is what makes ``finish_many`` == sequential
    ``finish`` exact (device moments are per-cell arithmetic and already
    batch-invariant)."""
    xm = np.asarray(x, np.float64).reshape(-1) - float(_MOM_SHIFT)
    return (np.float32(xm.sum()), np.float32((xm * xm).sum()))


def query_var_moments(x: np.ndarray, v: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side path-independent variance folds (sv, svx, svxx) of a
    query with per-sample variances ``v`` — the variance-mode companions
    of :func:`query_moments` (same float64 accumulation, same
    batch-invariance argument)."""
    xm = np.asarray(x, np.float64).reshape(-1) - float(_MOM_SHIFT)
    vv = np.asarray(v, np.float64).reshape(-1)
    return (np.float32(vv.sum()), np.float32((vv * xm).sum()),
            np.float32((vv * xm * xm).sum()))


def _pad_pow2(n: int, lo: int = 8) -> int:
    return max(lo, 1 << (max(n, 1) - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class ScoreBankPlan:
    """Device-resident tiling of a reference bank for the offline
    scorers: the bank sorted by ascending true length, split into
    ``block_k``-wide tiles each trimmed to its own padded width, already
    uploaded.  Build once per bank (``database.SeriesBank.score_plan``
    caches it) and reuse across verdicts — re-deriving it per call would
    re-upload the whole bank every ``finish()``.

    A plan over a 1-D ``mesh`` splits the bank's K axis over the mesh
    devices: the tiles sit on the devices in turn (the jnp scorer's
    dispatches then run where their tile is), and ``sharded`` holds the
    whole bank, in bank order, K-sharded for the offline kernel.
    """
    k: int
    inv: np.ndarray                     # [K] un-permutation of tile order
    tiles: Tuple[Tuple[jax.Array, jax.Array], ...]   # ([BK, M_t], [BK])
    mesh: Optional[jax.sharding.Mesh] = None
    sharded: Optional[Tuple[jax.Array, jax.Array]] = None  # [Kp, M], [Kp]


def _kernel_block(k: int) -> int:
    """Reference-tile width of the offline kernel for a K-row bank."""
    return min(128, _pad_pow2(k))


def shard_width(k: int, ndev: int, block: int) -> int:
    """K padded to ``ndev`` equal shards, each a whole number of
    ``block``-wide kernel tiles when it is wider than one tile."""
    w = -(-k // ndev)
    if w > block:
        w = -(-w // block) * block
    return w * ndev


def build_score_plan(series, lengths=None,
                     block_k: int = _SCORE_BLOCK_K,
                     mesh: Optional[jax.sharding.Mesh] = None
                     ) -> ScoreBankPlan:
    """Sort, tile, trim and upload a [K, M] bank for the offline
    scorers, on one device or K-sharded over a 1-D ``mesh``.
    Per-reference scores are independent of the ordering, tiling and
    sharding, so any plan of the same bank scores identically."""
    series = np.asarray(series, np.float32)
    k, m = series.shape
    lengths = np.full((k,), m, np.int32) if lengths is None \
        else np.asarray(lengths, np.int32)
    order = np.argsort(lengths, kind="stable")
    devices = [None] if mesh is None else list(mesh.devices.flat)

    def put(a, t):
        dev = devices[t % len(devices)]
        return jnp.asarray(a) if dev is None else jax.device_put(a, dev)
    tiles = []
    for t, lo in enumerate(range(0, k, block_k)):
        sel = order[lo: lo + block_k]
        m_t = min(m, max(8, -(-int(lengths[sel].max()) // 8) * 8))
        tiles.append((put(series[sel, :m_t], t), put(lengths[sel], t)))
    inv = np.empty((k,), np.int64)
    inv[order] = np.arange(k)
    sharded = None
    if mesh is not None:
        kp = shard_width(k, len(devices), _kernel_block(k))
        bank = np.zeros((kp, m), np.float32)
        bank[:k] = series
        lens = np.ones((kp,), np.int32)
        lens[:k] = lengths
        P = jax.sharding.PartitionSpec
        axis = mesh.axis_names[0]
        sharded = (
            jax.device_put(bank, jax.sharding.NamedSharding(
                mesh, P(axis, None))),
            jax.device_put(lens, jax.sharding.NamedSharding(mesh, P(axis))))
    return ScoreBankPlan(k=k, inv=inv, tiles=tuple(tiles), mesh=mesh,
                         sharded=sharded)


@functools.lru_cache(maxsize=None)
def _sharded_offline(mesh, variance: bool, approx: bool,
                     band: Optional[int], threshold: float, block_k: int,
                     interpret: bool):
    """The offline kernel shard_mapped over ``mesh``: each device scores
    its K shard of the bank against every (replicated) query, and the
    [J, Kp] outputs stay K-sharded until the caller pulls them."""
    from ..kernels.dtw import (score_bank_offline_kernel,
                               score_bank_offline_var_kernel)
    P = jax.sharding.PartitionSpec
    axis = mesh.axis_names[0]
    if variance:
        body = functools.partial(
            score_bank_offline_var_kernel, band=band, threshold=threshold,
            block_k=block_k, interpret=interpret, approx=approx)
        in_specs = (P(), P(), P(), P(axis, None), P(axis), P(), P(), P())
        n_out = 3
    else:
        body = functools.partial(score_bank_offline_kernel, band=band,
                                 block_k=block_k, interpret=interpret)
        in_specs = (P(), P(), P(axis, None), P(axis), P(), P())
        n_out = 2
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=(P(None, axis),) * n_out,
                                 check_vma=False))


def dtw_score_bank_many(xs, bank, lengths=None, xlens=None,
                        band: Optional[int] = None,
                        sx=None, sxx=None, *,
                        xvars=None, vstats=None,
                        threshold: float = 0.9,
                        prob_mode: str = "exact",
                        plan: Optional[ScoreBankPlan] = None,
                        use_kernel: Optional[bool] = None,
                        interpret: Optional[bool] = None,
                        block_k: int = _SCORE_BLOCK_K,
                        return_distances: bool = False):
    """Closed-end warp correlations of J queries against a padded bank in
    ONE dispatch -> float32 [J, K] (optionally also the DTW distances
    D(xlen_j, len_k) [J, K]).

    ``xs`` is [J, N] (padded; ``xlens`` holds true lengths, default N),
    ``bank`` [K, M] with ``lengths`` as everywhere else.  ``sx``/``sxx``
    are the per-query centered folds (:func:`query_moments`); when None
    they are computed here on the host.  Scores equal
    ``similarity_bank``'s host backtrack + correlation: bitwise-path on
    tie-free (dyadic-grid) data, to warp-path-tie tolerance elsewhere.

    Variance mode: passing ``xvars`` [J, N] (per-sample measurement
    variances; ``vstats`` [J, 3] = (sv, svx, svxx) folds optional, see
    :func:`query_var_moments`) switches to the seven-channel scorer and
    the return value becomes ``(scores, probs)`` (plus dists when
    ``return_distances``), where ``probs`` [J, K] is
    P[true warp correlation >= ``threshold``] per
    :func:`_prob_from_moments` — all-zero ``xvars`` reduces ``probs``
    to the point rule ``scores >= threshold`` exactly.
    ``prob_mode="approx"`` swaps in the single-proxy
    :func:`_prob_from_moments_approx` tail (the serving tick's
    probability model) — the calibration reference for pinning approx
    against exact offline; verdict paths keep the default exact tail.

    Routed to the Pallas offline kernel (``kernels.dtw.score``) on TPU
    backends — DP row and moment slabs pinned in VMEM per (query,
    ref-tile) program — and to the tiled jnp wavefront elsewhere;
    ``use_kernel``/``interpret`` exist so tests can pin kernel == jnp in
    interpret mode on CPU hosts.

    A ``plan`` built over a 1-D mesh (:func:`build_score_plan`) splits
    the work over its devices: the kernel path is one shard_mapped
    program in which each device scores its K shard of the bank, the
    jnp path runs each tile on the device that holds it.  Scores are
    bit-identical to the unsharded ones.
    """
    xs = np.asarray(xs, np.float32)
    if xs.ndim != 2:
        raise ValueError(f"xs must be [J, N], got shape {xs.shape}")
    j, n = xs.shape
    if xlens is None:
        xlens = np.full((j,), n, np.int32)
    xlens = np.asarray(xlens, np.int32)
    series = np.asarray(bank, np.float32)
    k, m = series.shape
    lengths = np.full((k,), m, np.int32) if lengths is None \
        else np.asarray(lengths, np.int32)
    if sx is None or sxx is None:
        folds = [query_moments(xs[i, :xlens[i]]) for i in range(j)]
        sx = np.asarray([f[0] for f in folds], np.float32)
        sxx = np.asarray([f[1] for f in folds], np.float32)
    if xvars is not None:
        xvars = np.asarray(xvars, np.float32)
        if xvars.shape != xs.shape:
            raise ValueError(f"xvars must match xs shape {xs.shape}, "
                             f"got {xvars.shape}")
        if vstats is None:
            vstats = np.asarray(
                [query_var_moments(xs[i, :xlens[i]], xvars[i, :xlens[i]])
                 for i in range(j)], np.float32)
        vstats = np.asarray(vstats, np.float32)
    if prob_mode not in ("exact", "approx"):
        raise ValueError(f"prob_mode must be 'exact' or 'approx', "
                         f"got {prob_mode!r}")
    if use_kernel is None:
        use_kernel = _kernel_backend()
    if k == 0:
        z = jnp.zeros((j, 0), jnp.float32)
        out = (z, z) if xvars is not None else (z,)
        out = out + (z,) if return_distances else out
        return out if len(out) > 1 else out[0]
    if use_kernel and plan is not None and plan.mesh is not None:
        if interpret is None:
            from ..kernels.common import default_interpret
            interpret = default_interpret()
        bank_sh, lens_sh = plan.sharded
        fn = _sharded_offline(plan.mesh, xvars is not None,
                              prob_mode == "approx", band, float(threshold),
                              _kernel_block(k), interpret)
        if xvars is not None:
            out = fn(xs, xvars, xlens, bank_sh, lens_sh, sx, sxx, vstats)
        else:
            out = fn(xs, xlens, bank_sh, lens_sh, sx, sxx)
        if bank_sh.shape[0] != k:
            out = tuple(o[:, :k] for o in out)
        if return_distances:
            return out
        return out[:2] if xvars is not None else out[0]
    if xvars is not None:
        if use_kernel:
            if interpret is None:
                from ..kernels.common import default_interpret
                interpret = default_interpret()
            if prob_mode == "approx":
                from ..kernels.dtw import \
                    score_bank_offline_var_approx_kernel as var_kernel
            else:
                from ..kernels.dtw import \
                    score_bank_offline_var_kernel as var_kernel
            scores, probs, dists = var_kernel(
                jnp.asarray(xs), jnp.asarray(xvars), jnp.asarray(xlens),
                jnp.asarray(series), jnp.asarray(lengths),
                jnp.asarray(sx), jnp.asarray(sxx), jnp.asarray(vstats),
                band=band, threshold=float(threshold),
                block_k=_kernel_block(k), interpret=interpret)
            return (scores, probs, dists) if return_distances \
                else (scores, probs)
        # jnp path: the simple tiled wavefront always (the windowed /
        # batched-verdict perf variants have no variance twins —
        # _score_tile_var supports the band mask directly).
        if plan is None:
            plan = build_score_plan(series, lengths, block_k)
        parts = []
        for lo in range(0, j, _SCORE_J_GROUP):
            hi = min(lo + _SCORE_J_GROUP, j)
            parts.append([
                _score_tile_var_many(
                    jnp.asarray(xs[lo:hi]), jnp.asarray(xvars[lo:hi]),
                    jnp.asarray(xlens[lo:hi]), tb, tl,
                    jnp.asarray(sx[lo:hi]), jnp.asarray(sxx[lo:hi]),
                    jnp.asarray(vstats[lo:hi]), band, float(threshold),
                    approx=prob_mode == "approx")
                for tb, tl in plan.tiles])
        jax.block_until_ready(parts)
        scores, probs, dists = (np.concatenate(
            [np.concatenate([np.asarray(p[i]) for p in grp], axis=1)
             for grp in parts], axis=0)[:, plan.inv] for i in range(3))
        return (scores, probs, dists) if return_distances \
            else (scores, probs)
    if use_kernel:
        if interpret is None:
            from ..kernels.common import default_interpret
            interpret = default_interpret()
        from ..kernels.dtw import score_bank_offline_kernel
        scores, dists = score_bank_offline_kernel(
            jnp.asarray(xs), jnp.asarray(xlens), jnp.asarray(series),
            jnp.asarray(lengths), jnp.asarray(sx), jnp.asarray(sxx),
            band=band, block_k=_kernel_block(k), interpret=interpret)
        return (scores, dists) if return_distances else scores
    # jnp path: tile the bank in ascending-length order with a trimmed
    # per-tile width (ragged banks pay for their own lengths, not the
    # global max) and dispatch the tiles asynchronously — the [4, BK, M_t]
    # per-step working set stays cache-resident on CPU hosts, which is
    # where this path runs.  Per-reference results are independent of the
    # ordering/tiling, so the column un-permutation below is exact.
    if plan is None:
        plan = build_score_plan(series, lengths, block_k)
    elif plan.k != k:
        raise ValueError(
            f"ScoreBankPlan is for a {plan.k}-reference bank but "
            f"{k} references were passed — plans are bank-specific "
            "(rebuild via build_score_plan / SeriesBank.score_plan)")
    # dispatch per (job-group, tile) WITHOUT blocking in between: the
    # independent wavefronts overlap across host cores via async
    # dispatch, which an in-program lax.map over all J would serialize.
    # Small groups keep the dispatch count O(J/4 * K/BK), not O(J*K).
    #
    # Banded verdicts take the windowed wavefront instead: per-job work
    # drops from O((N+M)*M) to O((N+M)*w) and the [4, BK, w] window
    # carry is small enough to vmap whole batches into one dispatch, so
    # the group is the batch (this is what makes finish_many actually
    # faster than sequential finishes on a one-core host, where the
    # full-width wavefront is compute-bound either way).
    windowed = []
    if band is not None:
        for tb, tl in plan.tiles:
            m_t = int(tb.shape[1])
            w = _window_width(xlens, np.asarray(tl), m_t, band)
            windowed.append(w if w + 16 <= m_t else None)
    parts = []
    # banded calls are verdict-shaped: the whole batch goes out in ONE
    # call per tile (windowed wavefront on wide tiles, grouped-vmap
    # full-width scorer on narrow ones, both internally grouped), with
    # the scan truncated at the last live anti-diagonal of the TRUE
    # query lengths (bucketed to 16 so repeat drains reuse jit shapes).
    group = j if band is not None else _SCORE_J_GROUP
    n_live = int(xlens.max()) if j else 0
    for lo in range(0, j, group):
        hi = min(lo + group, j)
        xs_j = jnp.asarray(xs[lo:hi])
        xlens_j = jnp.asarray(xlens[lo:hi])
        sx_j = jnp.asarray(sx[lo:hi])
        sxx_j = jnp.asarray(sxx[lo:hi])
        parts.append([
            _score_tile_banded_many(xs_j, xlens_j, tb, tl, sx_j, sxx_j,
                                    band, windowed[ti], _VERDICT_GROUP)
            if windowed and windowed[ti] is not None else
            _score_tile_verdict(xs_j, xlens_j, tb, tl, sx_j, sxx_j, band,
                                min(n + int(tb.shape[1]) - 1,
                                    -(-(n_live + int(tb.shape[1]) - 1)
                                      // 16) * 16))
            if band is not None else
            _score_tile_many(xs_j, xlens_j, tb, tl, sx_j, sxx_j, band)
            for ti, (tb, tl) in enumerate(plan.tiles)])
    jax.block_until_ready(parts)
    scores = np.concatenate(
        [np.concatenate([np.asarray(p[0]) for p in group], axis=1)
         for group in parts], axis=0)[:, plan.inv]
    dists = np.concatenate(
        [np.concatenate([np.asarray(p[1]) for p in group], axis=1)
         for group in parts], axis=0)[:, plan.inv]
    return (scores, dists) if return_distances else scores


def dtw_score_bank(x, bank, lengths=None, band: Optional[int] = None, *,
                   plan: Optional[ScoreBankPlan] = None,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None,
                   block_k: int = _SCORE_BLOCK_K,
                   return_distances: bool = False):
    """One query against the whole bank -> float32 [K] closed-end warp
    correlations (the matrix-free ``similarity_bank`` engine).  See
    :func:`dtw_score_bank_many`; this is its J == 1 column."""
    x = np.asarray(x, np.float32).reshape(-1)
    out = dtw_score_bank_many(
        x[None], bank, lengths, None, band, plan=plan,
        use_kernel=use_kernel, interpret=interpret, block_k=block_k,
        return_distances=return_distances)
    return (out[0][0], out[1][0]) if return_distances else out[0]


def dtw_score_pairs(xs, ys, xlens=None, ylens=None,
                    band: Optional[int] = None, *,
                    return_distances: bool = False):
    """Pairwise closed-end warp correlations -> float32 [P]: query p vs
    reference p, ragged on both sides (the matrix-free engine behind
    ``match_application``'s per-parameter-set scoring)."""
    xs = np.asarray(xs, np.float32)
    ys = jnp.asarray(ys, jnp.float32)
    p, n = xs.shape
    xl = np.full((p,), n, np.int32) if xlens is None \
        else np.asarray(xlens, np.int32)
    yl = _lengths_or_full(None if ylens is None else jnp.asarray(ylens),
                          *ys.shape)
    folds = [query_moments(xs[i, :xl[i]]) for i in range(p)]
    sx = np.asarray([f[0] for f in folds], np.float32)
    sxx = np.asarray([f[1] for f in folds], np.float32)
    scores, dists = _score_pairs_impl(
        jnp.asarray(xs), ys, jnp.asarray(xl), yl, jnp.asarray(sx),
        jnp.asarray(sxx), band)
    return (scores, dists) if return_distances else scores


@dataclasses.dataclass(frozen=True)
class DtwBankState:
    """Streaming DP state of one query against a padded [K, M] bank.

    Immutable: :func:`dtw_bank_extend` returns a new state.  ``row`` holds
    D[n-1, :] per reference (all +inf before the first sample); ``n`` is
    the number of query samples consumed so far.
    """
    row: jax.Array                    # [K, M] float32
    n: int                            # samples consumed
    bank: jax.Array                   # [K, M] float32
    lengths: jax.Array                # [K] int32
    band: Optional[int] = None
    query_len: Optional[int] = None   # required (and fixed) when banded

    def __len__(self) -> int:
        return int(self.bank.shape[0])

    def distances(self) -> jax.Array:
        """D(n, len_k) against every *complete* reference -> [K].

        Equals ``dtw_distance_bank(x[:n], bank, lengths)`` for the consumed
        prefix x[:n] (banded: once n == query_len — mid-stream banded
        values use the corridor anchored at the full query length, which
        a shorter one-shot solve would place differently); +inf before any
        sample arrived.
        """
        return jnp.take_along_axis(
            self.row, (self.lengths - 1)[:, None].astype(jnp.int32),
            axis=1)[:, 0]

    def prefix_distances(self) -> jax.Array:
        """Open-end distances min_j D(n, j) over true columns -> [K].

        The best alignment of the consumed prefix against *any* prefix of
        each reference — monotonically non-decreasing in ``n`` (every
        longer-prefix path extends a shorter one with non-negative cost),
        which is what makes early pruning sound.
        """
        m = self.row.shape[1]
        masked = jnp.where(jnp.arange(m, dtype=jnp.int32)[None, :]
                           < self.lengths[:, None], self.row, _INF)
        return jnp.min(masked, axis=1)

    # -- (de)hydration (crash-safe serving, serve.recovery) ------------------
    def dehydrate(self) -> Dict[str, np.ndarray]:
        """Host-resident dict of the full streaming state — flat string
        keys, numpy leaves, so it drops straight into a dict-nested
        checkpoint tree (``checkpoint.load_checkpoint_tree``).  Scalars
        ride as 0-d/1-element arrays; ``hydrate`` reverses exactly."""
        meta = np.asarray([self.n,
                           -1 if self.band is None else self.band,
                           -1 if self.query_len is None
                           else self.query_len], np.int64)
        return {"row": np.asarray(self.row), "bank": np.asarray(self.bank),
                "lengths": np.asarray(self.lengths), "meta": meta}

    @staticmethod
    def hydrate(tree: Dict[str, np.ndarray]) -> "DtwBankState":
        """Rebuild a :class:`DtwBankState` from :meth:`dehydrate` output
        (device placement via plain ``jnp.asarray`` — callers needing a
        sharded bank re-place afterwards).  The round trip is bitwise:
        every leaf is stored verbatim, nothing is recomputed."""
        n, band, qlen = (int(v) for v in np.asarray(tree["meta"]))
        return DtwBankState(
            row=jnp.asarray(tree["row"]), n=n,
            bank=jnp.asarray(tree["bank"]),
            lengths=jnp.asarray(tree["lengths"]),
            band=None if band < 0 else band,
            query_len=None if qlen < 0 else qlen)


def dtw_bank_init(bank: jax.Array, lengths: Optional[jax.Array] = None,
                  band: Optional[int] = None,
                  query_len: Optional[int] = None) -> DtwBankState:
    """Fresh streaming state for one query against a padded [K, M] bank.

    ``query_len`` (the expected total query length) is required for the
    banded variant: the Sakoe-Chiba corridor of row i is positioned
    relative to the *full* query, so an open-ended banded stream is
    ill-defined without it.
    """
    bank = jnp.asarray(bank, jnp.float32)
    k, m = bank.shape
    if band is not None and query_len is None:
        raise ValueError("banded streaming needs query_len (the band "
                         "geometry depends on the full query length)")
    return DtwBankState(row=jnp.full((k, m), _INF), n=0, bank=bank,
                        lengths=_lengths_or_full(lengths, k, m),
                        band=band, query_len=query_len)


def dtw_bank_extend(state: DtwBankState, chunk: jax.Array,
                    collect_rows: bool = False
                    ) -> Tuple[DtwBankState, Optional[jax.Array]]:
    """Consume one chunk of query samples; one jitted dispatch.

    Returns ``(new_state, rows)`` where ``rows`` is the [c, K, M] stack of
    DP rows produced by this chunk (for warp-based prefix scoring) when
    ``collect_rows``, else None.  The chunk is padded to a power-of-two
    bucket internally so arbitrary chunkings reuse a few compiled shapes.
    """
    chunk = jnp.asarray(chunk, jnp.float32).reshape(-1)
    c = int(chunk.shape[0])
    if c == 0:
        return state, (jnp.zeros((0,) + state.row.shape) if collect_rows
                       else None)
    cp = _chunk_bucket(c)
    padded = jnp.concatenate([chunk, jnp.zeros((cp - c,), jnp.float32)]) \
        if cp != c else chunk
    qlen = state.query_len if state.query_len is not None else 0
    rows, ns, collected = _bank_extend_many(
        state.row[None], jnp.asarray([state.n], jnp.int32), state.bank,
        state.lengths, padded[None], jnp.asarray([c], jnp.int32),
        jnp.asarray([qlen], jnp.int32), state.band, collect_rows)
    new = dataclasses.replace(state, row=rows[0], n=state.n + c)
    return new, (collected[:c, 0] if collect_rows else None)


# ---------------------------------------------------------------------------
# Backtracking / warping (numpy; O(N+M), data-dependent)
# ---------------------------------------------------------------------------

def backtrack(D: np.ndarray) -> np.ndarray:
    """Minimum-distance path through D from (0,0) to (N-1,M-1).

    Returns an int array [P, 2] of (i, j) pairs, monotonically
    non-decreasing in both coordinates.
    """
    D = np.asarray(D)
    n, m = D.shape
    i, j = n - 1, m - 1
    path = [(i, j)]
    while i > 0 or j > 0:
        if i == 0:
            j -= 1
        elif j == 0:
            i -= 1
        else:
            candidates = (D[i - 1, j - 1], D[i - 1, j], D[i, j - 1])
            k = int(np.argmin(candidates))
            if k == 0:
                i, j = i - 1, j - 1
            elif k == 1:
                i -= 1
            else:
                j -= 1
        path.append((i, j))
    return np.asarray(path[::-1], dtype=np.int64)


def warp_to(y: np.ndarray, path: np.ndarray, n: int) -> np.ndarray:
    """Build Y' (length n, aligned with X) from Y by repeating elements
    along the DTW path (paper §3.1.2: "Y' is always made from Y by
    repeating some of its elements based on D(X,Y)")."""
    yp = np.empty(n, dtype=np.asarray(y).dtype)
    for i, j in path:          # path is sorted by i; later pairs overwrite
        yp[i] = y[j]
    return yp


def dtw_warp(x: np.ndarray, y: np.ndarray,
             band: Optional[int] = None) -> Tuple[np.ndarray, float]:
    """Full pipeline: DTW -> backtrack -> warped Y' and distance D(N,M)."""
    x = jnp.asarray(x, jnp.float32)
    yj = jnp.asarray(y, jnp.float32)
    D = np.asarray(dtw_matrix(x, yj) if band is None
                   else dtw_matrix_banded(x, yj, band))
    path = backtrack(D)
    return warp_to(np.asarray(y), path, len(np.asarray(x))), float(D[-1, -1])
