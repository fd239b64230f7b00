"""Plain numpy reference of the tuner's answers, in float64.

Written from the paper's definitions and the service's documented
semantics, not from the program (it imports nothing of it and takes
nothing it made):

* the paper's pre-processing of the bank: a 6th-order Chebyshev type-I
  low-pass (1 dB ripple, cutoff 0.125 of Nyquist) designed here, run
  forward and backward with odd reflection padding, then [0, 1]
  normalization of each series;
* the causal filter of the in-flight samples: the same low-pass run
  forward only, from a zero state;
* dynamic time warping (paper Eq. 1-2) with the Sakoe-Chiba band centred
  on column ``i * (len - 1) // (qlen - 1)`` of query row ``i``, as the
  full matrix with a backtrack (ties: diagonal, then vertical, then
  horizontal); an in-flight prefix ends at the best column of its last
  row (open end), a finished query at the reference's last column;
* the warped reference keeps one sample per query row (the last along
  the path), and the score is its Pearson correlation with the query
  (paper Eq. 3), with the service's degenerate conventions;
* the match probability P[true correlation >= threshold] under the
  query's per-sample variances: first-order error propagation through
  the correlation, after disattenuating it for the noise, in its exact
  form and in the approximate form that reconstructs two of the
  variance sums from a regression along the path.

``Precision`` says how values are stored: ``FLOAT64`` everywhere is the
reference; ``BF16_STATE`` computes each step in float32 and stores the
inputs, the DP rows and the filtered series in bfloat16 — the control,
the reference put in the program's place one precision below what the
configuration states.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

import numpy as np

#: the paper's low-pass (order, ripple dB, cutoff as a fraction of Nyquist)
FILTER = (6, 1.0, 0.125)
#: correlation moments are taken about this centre (the approximate
#: probability tail is defined on sums centred here)
CENTRE = 0.5


@dataclasses.dataclass(frozen=True)
class Precision:
    name: str
    compute: type

    def store(self, a):
        a = np.asarray(a, self.compute)
        if self.name == "bf16_state":
            import ml_dtypes
            return a.astype(ml_dtypes.bfloat16).astype(self.compute)
        return a


FLOAT64 = Precision("float64", np.float64)
BF16_STATE = Precision("bf16_state", np.float32)


# -- filtering ----------------------------------------------------------------

def cheby1(order: int, ripple_db: float, cutoff: float):
    """Digital Chebyshev type-I low-pass ``(b, a)``: analog prototype,
    pre-warped cutoff, bilinear transform."""
    eps = math.sqrt(10.0 ** (0.1 * ripple_db) - 1.0)
    mu = math.asinh(1.0 / eps) / order
    k = np.arange(1, order + 1)
    theta = np.pi * (2.0 * k - 1.0) / (2.0 * order)
    poles = -np.sinh(mu) * np.sin(theta) + 1j * np.cosh(mu) * np.cos(theta)
    gain = np.real(np.prod(-poles))
    if order % 2 == 0:
        gain /= math.sqrt(1.0 + eps * eps)
    warped = 4.0 * math.tan(np.pi * cutoff / 2.0)       # 2 fs tan(pi f/fs)
    poles = poles * warped
    gain = gain * warped ** order
    zp = (4.0 + poles) / (4.0 - poles)                   # bilinear, fs = 2
    gain = gain * np.real(np.prod(1.0 / (4.0 - poles)))
    b = gain * np.real(np.poly(np.full(order, -1.0)))
    a = np.real(np.poly(zp))
    return b / a[0], a / a[0]


def lfilter(x, prec: Precision = FLOAT64):
    """Causal low-pass along the last axis of ``x`` [B, T] from a zero
    state (direct form II transposed)."""
    b, a = (c.astype(prec.compute) for c in cheby1(*FILTER))
    x = np.asarray(x, prec.compute)
    z = np.zeros(x.shape[:-1] + (len(b) - 1,), prec.compute)
    y = np.empty_like(x)
    for t in range(x.shape[-1]):
        xt = x[..., t]
        yt = b[0] * xt + z[..., 0]
        z = np.concatenate([z[..., 1:], np.zeros_like(z[..., :1])], axis=-1) \
            + b[1:] * xt[..., None] - a[1:] * yt[..., None]
        y[..., t] = yt
    return prec.store(y)


def preprocess(x, prec: Precision = FLOAT64):
    """Zero-phase low-pass (forward, then backward, with odd reflection
    padding of up to 3 x order samples at each end) and [0, 1]
    normalization of each row of ``x`` [B, T]."""
    x = np.asarray(x, prec.compute)
    t = x.shape[-1]
    pad = min(3 * FILTER[0], t - 1)
    if pad > 0:
        left = 2 * x[:, :1] - x[:, 1: pad + 1][:, ::-1]
        right = 2 * x[:, -1:] - x[:, -pad - 1: -1][:, ::-1]
        x = np.concatenate([left, x, right], axis=1)
    y = lfilter(lfilter(x, prec)[:, ::-1], prec)[:, ::-1]
    if pad > 0:
        y = y[:, pad: pad + t]
    lo = y.min(axis=1, keepdims=True)
    hi = y.max(axis=1, keepdims=True)
    return prec.store((y - lo) / np.maximum(hi - lo, 1e-8))


@dataclasses.dataclass(frozen=True)
class Bank:
    series: np.ndarray            # [K, M], edge-padded
    lengths: np.ndarray           # [K]
    labels: tuple                 # [K] workload of each row


def build_bank(raw: Sequence[np.ndarray], labels: Sequence[str],
               prec: Precision = FLOAT64) -> Bank:
    """The reference bank: every raw profiled series preprocessed, rows
    of equal length together."""
    lengths = np.asarray([len(s) for s in raw], np.int64)
    m = int(lengths.max())
    out = np.empty((len(raw), m), prec.compute)
    for n in np.unique(lengths):
        idx = np.nonzero(lengths == n)[0]
        block = preprocess(np.stack([np.asarray(raw[i], np.float64)
                                     for i in idx]), prec)
        out[idx, :n] = block
        out[idx, n:] = block[:, -1:]
    return Bank(out, lengths, tuple(labels))


# -- alignment ------------------------------------------------------------------

def warped(x, bank: Bank, *, qlen: int, band: Optional[int], open_end: bool,
           prec: Precision = FLOAT64):
    """DTW of the query ``x`` [n] against every bank row, as the full
    matrix kept by its backtrack pointers -> the warped references
    ``yp`` [K, n] (``yp[k, i]`` is the reference sample aligned last
    with query row ``i``) and a [K] mask of references with a finite
    path.

    ``qlen`` anchors the band centre (the expected length of an in-flight
    job, the true length of a finished one)."""
    x = prec.store(x)
    y = prec.store(bank.series)
    lens = bank.lengths
    k_n, m = y.shape
    n = x.shape[0]
    kk = np.arange(k_n)
    if band is None:
        width = m
        lo_of = lambda i: np.zeros(k_n, np.int64)          # noqa: E731
    else:
        width = 2 * band + 1
        lo_of = lambda i: (i * (lens - 1)) // max(qlen - 1, 1) - band  # noqa
    cols = np.arange(width)
    inf = np.inf
    prev = None
    prev_lo = None
    ptrs = np.empty((n, k_n, width), np.int8)
    los = np.empty((n, k_n), np.int64)
    for i in range(n):
        lo = lo_of(i)
        j = lo[:, None] + cols[None, :]                          # [K, W]
        ok = (j >= 0) & (j < lens[:, None])
        jc = np.clip(j, 0, m - 1)
        d = np.abs(x[i] - y[kk[:, None], jc])
        d = np.where(ok, d, 0.0)
        if i == 0:
            vert = np.full(j.shape, inf)
            diag = np.where(j == 0, 0.0, inf)                    # corner
        else:
            def at(col):
                off = col - prev_lo[:, None]
                inside = (off >= 0) & (off < width) & (col >= 0)
                return np.where(inside, prev[kk[:, None],
                                             np.clip(off, 0, width - 1)],
                                inf)
            vert = at(j)
            diag = at(j - 1)
        mn = np.where(ok, np.minimum(diag, vert), inf)
        # D[i, j] = d + min(mn_j, D[i, j-1]) along the row: with C the
        # running sum of d, D = C + running min of (mn + d - C).
        c = np.cumsum(d, axis=1)
        with np.errstate(invalid="ignore"):
            row = c + np.minimum.accumulate(mn + d - c, axis=1)
        row = prec.store(np.where(ok, row, inf))
        horiz = np.concatenate([np.full((k_n, 1), inf), row[:, :-1]], axis=1)
        sel_diag = diag <= np.minimum(vert, horiz)
        sel_vert = ~sel_diag & (vert <= horiz)
        ptrs[i] = np.where(sel_diag, 0, np.where(sel_vert, 1, 2))
        los[i] = lo
        prev, prev_lo = row, lo
    # end column of the last row
    if open_end:
        wend = np.argmin(prev, axis=1)              # first best column
        jend = prev_lo + wend
        finite = np.isfinite(prev[kk, wend])
    else:
        jend = lens - 1
        off = jend - prev_lo
        finite = (off >= 0) & (off < width)
        finite &= np.isfinite(prev[kk, np.clip(off, 0, width - 1)])
    # backtrack, all references at once
    ii = np.full(k_n, n - 1)
    jj = jend.astype(np.int64).copy()
    yp = np.empty((k_n, n), prec.compute)
    yp[:, n - 1] = y[kk, np.clip(jj, 0, m - 1)]
    live = (ii > 0) | (jj > 0)
    while live.any():
        p = ptrs[ii, kk, np.clip(jj - los[ii, kk], 0, width - 1)]
        move_i = live & ((jj == 0) | ((ii > 0) & (p <= 1)))
        move_j = live & ((ii == 0) | ((jj > 0) & ((p == 0) | (p == 2))))
        move_j &= ~((jj == 0))
        move_i &= ~((ii == 0))
        ii = ii - move_i
        jj = jj - move_j
        new_row = move_i
        yp[kk[new_row], ii[new_row]] = y[kk[new_row], jj[new_row]]
        live = (ii > 0) | (jj > 0)
    return yp, finite


# -- scores and probabilities -----------------------------------------------------

def _degenerate(s, ss, n):
    """A series is constant when its variance is at most 1e-5 of its
    energy about the centre (the service's convention)."""
    return (ss - s * s / n) <= 1e-5 * (ss + s * s / n) + 1e-12


def sums(x, yp, v=None) -> Dict[str, np.ndarray]:
    """Path sums about ``CENTRE``: query (sx, sxx), warped reference
    (sy, syy, sxy) and, with variances, (sv, svx, svxx, svy, svyy,
    svxy)."""
    xc = np.asarray(x, np.float64) - CENTRE
    yc = np.asarray(yp, np.float64) - CENTRE
    out = dict(n=float(xc.shape[0]), sx=xc.sum(), sxx=(xc * xc).sum(),
               sy=yc.sum(axis=1), syy=(yc * yc).sum(axis=1),
               sxy=(yc * xc).sum(axis=1))
    if v is not None:
        v = np.asarray(v, np.float64)
        out.update(sv=v.sum(), svx=(v * xc).sum(), svxx=(v * xc * xc).sum(),
                   svy=(yc * v).sum(axis=1), svyy=(yc * yc * v).sum(axis=1),
                   svxy=(yc * (v * xc)).sum(axis=1))
    return out


def correlation(s) -> np.ndarray:
    """Pearson correlation of the query with each warped reference, with
    the degenerate conventions: 1.0 for two equal constant series, 0.0
    when either is constant otherwise."""
    n = s["n"]
    vx = max(s["sxx"] - s["sx"] ** 2 / n, 0.0)
    vy = np.maximum(s["syy"] - s["sy"] ** 2 / n, 0.0)
    cov = s["sxy"] - s["sx"] * s["sy"] / n
    den = np.sqrt(vx * vy)
    r = np.clip(cov / np.where(den > 0, den, 1.0), -1.0, 1.0)
    dx = _degenerate(s["sx"], s["sxx"], n)
    dy = _degenerate(s["sy"], s["syy"], n)
    both = dx & dy & (np.abs(s["sx"] - s["sy"]) / n < 1e-6)
    return np.where(dx | dy, np.where(both, 1.0, 0.0), r)


_erfc = np.frompyfunc(math.erfc, 1, 1)


def _tail(r, sigma, vx, sv, threshold):
    """P[true correlation >= threshold]: ``r`` disattenuated for the
    noise the variances add to the query's spread (at most 2x), then the
    normal tail; with no spread the point rule."""
    den = np.clip(vx - sv, 0.25 * vx, vx)
    g = np.where(den > 0, np.sqrt(vx / np.where(den > 0, den, 1.0)), 1.0)
    r_hat = np.clip(r * g, -1.0, 1.0)
    z = (r_hat - threshold) / np.where(sigma > 0, sigma, 1.0)
    phi = 0.5 * _erfc(-z / math.sqrt(2.0)).astype(np.float64)
    return np.where(sigma > 0, phi, (r_hat >= threshold).astype(np.float64))


def probability(s, threshold: float, approx: bool = False) -> np.ndarray:
    """Match probability of each warped reference.  Exact: the variance
    of r is sum_i v_i (dr/dx_i)^2 with dr/dx_i = (y_i - mean y) c -
    r (x_i - mean x) / vx, c = 1 / sqrt(vx vy).  Approximate: the sums
    over v y^2 and v x y are rebuilt from the carried sum over v y and a
    regression y ~ alpha + beta x along the path."""
    n = s["n"]
    r = correlation(s)
    vx = max(s["sxx"] - s["sx"] ** 2 / n, 0.0)
    vy = np.maximum(s["syy"] - s["sy"] ** 2 / n, 0.0)
    cov = s["sxy"] - s["sx"] * s["sy"] / n
    den = np.sqrt(vx * vy)
    svx_ = vx if vx > 0 else 1.0
    c = 1.0 / np.where(den > 0, den, 1.0)
    # dr/dx_i = a + 2 b x_i + c y_i on centred values
    a = -c * s["sy"] / n + r * s["sx"] / (n * svx_)
    b = -r / (2.0 * svx_)
    sv, svx, svxx, svy = s["sv"], s["svx"], s["svxx"], s["svy"]
    if approx:
        beta = cov / svx_
        alpha = (s["sy"] - beta * s["sx"]) / n
        sv_safe = sv if sv > 0 else 1.0
        resid = svy - (alpha * sv + beta * svx)
        svxy = alpha * svx + beta * svxx + (svx / sv_safe) * resid
        sige2 = np.maximum(vy - cov * cov / svx_, 0.0) / n
        svyy = np.maximum(alpha * alpha * sv + 2.0 * alpha * beta * svx
                          + beta * beta * svxx
                          + 2.0 * (alpha + beta * svx / sv_safe) * resid
                          + sv * sige2, 0.0)
    else:
        svxy, svyy = s["svxy"], s["svyy"]
    var_r = (a * a * sv + 4.0 * a * b * svx + 4.0 * b * b * svxx
             + 2.0 * a * c * svy + 4.0 * b * c * svxy + c * c * svyy)
    sigma = np.sqrt(np.maximum(var_r, 0.0))
    return _tail(r, sigma, vx, sv, threshold)


def reduce(values: np.ndarray, labels: Sequence[str], floor: float = -1.0,
           finite: Optional[np.ndarray] = None) -> Dict[str, float]:
    """Best value per workload (bank rows without a finite path left
    out)."""
    out: Dict[str, float] = {}
    for k, lbl in enumerate(labels):
        val = float(values[k])
        if finite is not None and not finite[k]:
            val = floor
        out[lbl] = max(out.get(lbl, floor), val)
    return out


def answer(x_raw, v, bank: Bank, *, n: int, qlen: int, band: Optional[int],
           final: bool, threshold: float, prob: Optional[str],
           prec: Precision = FLOAT64):
    """Everything the service answers for one job after ``n`` samples:
    [K] scores (and probabilities) from the causally filtered prefix,
    open-ended in flight and closed for a finished job (whose band then
    follows its true length)."""
    xq = lfilter(np.asarray(x_raw[:n], np.float64)[None], prec)[0]
    yp, finite = warped(xq, bank, qlen=n if final else qlen, band=band,
                        open_end=not final, prec=prec)
    vv = None if v is None else np.asarray(v[:n], np.float64)
    s = sums(xq, yp, vv)
    out = dict(scores=np.where(finite, correlation(s), np.nan),
               finite=finite)
    if prob is not None:
        out["probs"] = np.where(
            finite, probability(s, threshold, approx=prob == "approx"),
            np.nan)
    return out
