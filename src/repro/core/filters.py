"""Chebyshev type-I low-pass filtering of utilization time series.

The paper de-noises every captured CPU-utilization series with a 6th-order
low-pass Chebyshev filter before storing/matching (§3.1.1, §4).  We design
the filter ourselves (analog Chebyshev-I prototype -> frequency pre-warp ->
bilinear transform) so the hot path has no scipy dependency, and apply it
either with a lax.scan (direct-form-II-transposed, batched over series) or
with the Pallas IIR kernel in ``repro.kernels.iir``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "cheby1_design",
    "lfilter",
    "filtfilt",
    "denoise",
    "normalize01",
    "preprocess",
    "preprocess_bank",
    "StreamingFilter",
]


# ---------------------------------------------------------------------------
# Filter design (numpy, runs once at trace time)
# ---------------------------------------------------------------------------

def _cheby1_analog_prototype(order: int, ripple_db: float):
    """Poles/gain of the analog Chebyshev-I prototype (cutoff 1 rad/s)."""
    if order < 1:
        raise ValueError("order must be >= 1")
    eps = np.sqrt(10.0 ** (0.1 * ripple_db) - 1.0)
    mu = np.arcsinh(1.0 / eps) / order
    k = np.arange(1, order + 1)
    theta = np.pi * (2.0 * k - 1.0) / (2.0 * order)
    poles = -np.sinh(mu) * np.sin(theta) + 1j * np.cosh(mu) * np.cos(theta)
    gain = np.real(np.prod(-poles))
    if order % 2 == 0:  # even order: passband sits at -ripple dB at DC
        gain /= np.sqrt(1.0 + eps * eps)
    return poles, gain


def cheby1_design(order: int, ripple_db: float, cutoff: float) -> Tuple[np.ndarray, np.ndarray]:
    """Digital Chebyshev-I low-pass ``(b, a)``.

    ``cutoff`` is the normalized cutoff in (0, 1), as a fraction of the
    Nyquist frequency (scipy convention).  Returns float64 coefficient
    arrays of length ``order + 1``.
    """
    if not 0.0 < cutoff < 1.0:
        raise ValueError(f"cutoff must be in (0,1), got {cutoff}")
    poles, gain = _cheby1_analog_prototype(order, ripple_db)

    # Pre-warp and scale the prototype (lp2lp), then bilinear transform.
    fs = 2.0
    warped = 2.0 * fs * np.tan(np.pi * cutoff / fs)
    poles = poles * warped
    gain = gain * warped ** order

    fs2 = 2.0 * fs
    z_digital = np.full(order, -1.0 + 0j)          # zeros map to z = -1
    p_digital = (fs2 + poles) / (fs2 - poles)
    gain = gain * np.real(np.prod(1.0 / (fs2 - poles)))

    b = gain * np.real(np.poly(z_digital))
    a = np.real(np.poly(p_digital))
    return b.astype(np.float64), a.astype(np.float64)


# ---------------------------------------------------------------------------
# Filter application (jax)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def _lfilter_scan(b: jax.Array, a: jax.Array, x: jax.Array) -> jax.Array:
    """Direct-form-II-transposed IIR over the last axis. x: [..., T]."""
    n = b.shape[0]
    batch_shape = x.shape[:-1]
    in_dtype = x.dtype
    x = x.astype(jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    xf = x.reshape((-1, x.shape[-1]))            # [B, T]
    B = xf.shape[0]
    state0 = jnp.zeros((B, n - 1), dtype=xf.dtype)

    b_ = b.astype(xf.dtype)
    a_ = a.astype(xf.dtype)

    def step(state, xt):                          # xt: [B]
        yt = b_[0] * xt + state[:, 0]
        # z_i <- b_{i+1} x - a_{i+1} y + z_{i+1}
        nxt = (b_[1:][None, :] * xt[:, None]
               - a_[1:][None, :] * yt[:, None]
               + jnp.pad(state[:, 1:], ((0, 0), (0, 1))))
        return nxt, yt

    _, y = jax.lax.scan(step, state0, jnp.moveaxis(xf, -1, 0))
    y = jnp.moveaxis(y, 0, -1).reshape(batch_shape + (x.shape[-1],))
    return y.astype(in_dtype)


def lfilter(b: np.ndarray, a: np.ndarray, x: jax.Array) -> jax.Array:
    """Apply IIR filter along the last axis (normalizes by a[0])."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64) / a[0]
    a = a / a[0]
    return _lfilter_scan(jnp.asarray(b), jnp.asarray(a), x)


def filtfilt(b: np.ndarray, a: np.ndarray, x: jax.Array) -> jax.Array:
    """Zero-phase filtering: forward pass, reverse, forward, reverse.

    Simple odd-reflection padding at both ends to suppress edge transients.
    """
    T = x.shape[-1]
    pad = min(3 * (max(len(a), len(b)) - 1), T - 1)
    if pad > 0:
        left = 2 * x[..., :1] - x[..., 1:pad + 1][..., ::-1]
        right = 2 * x[..., -1:] - x[..., -pad - 1:-1][..., ::-1]
        xp = jnp.concatenate([left, x, right], axis=-1)
    else:
        xp = x
    y = lfilter(b, a, xp)
    y = lfilter(b, a, y[..., ::-1])[..., ::-1]
    if pad > 0:
        y = y[..., pad:pad + T]
    return y


# ---------------------------------------------------------------------------
# Streaming (stateful causal) filtering
# ---------------------------------------------------------------------------

@jax.jit
def _lfilter_scan_carry(b: jax.Array, a: jax.Array, x: jax.Array,
                        zi: jax.Array, nvalid: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """One DF2T pass over a (padded) chunk with explicit state in/out.

    x: [T] chunk, zi: [n-1] filter state, nvalid: samples of x that are
    real — the state freezes after them, so padded tails never leak into
    the carried state (y's tail is garbage; callers slice).  Returns
    (y [T], zf [n-1]).  DF2T is causal, so filtering chunk-by-chunk with
    the carried state is *exactly* the one-shot :func:`lfilter` of the
    concatenated signal — the invariant the streaming service leans on.
    """
    def step(state, inp):
        xt, s = inp
        yt = b[0] * xt + state[0]
        nxt = b[1:] * xt - a[1:] * yt + jnp.pad(state[1:], (0, 1))
        return jnp.where(s < nvalid, nxt, state), yt

    zf, y = jax.lax.scan(
        step, zi, (x, jnp.arange(x.shape[0], dtype=jnp.int32)))
    return y, zf


class StreamingFilter:
    """Causal Chebyshev de-noise for in-flight series, chunk by chunk.

    The paper pipeline's :func:`filtfilt` is zero-phase and therefore
    anti-causal — it needs the whole series.  A job being matched *while it
    executes* only ever has a prefix, so the online path uses the causal
    forward filter with its direct-form-II-transposed state carried across
    chunks: any chunking of the input produces the same output as one
    one-shot :func:`lfilter` call (DTW downstream absorbs the filter's
    group delay).  Utilization series are already on the [0, 1] scale, so
    no running normalization is applied.

    Chunks are padded to power-of-two buckets before the jitted scan (the
    state freezes after the valid samples), so arbitrary tick sizes reuse
    a handful of compiled shapes instead of tracing per length.
    """

    def __init__(self, order: int = None, ripple_db: float = None,
                 cutoff: float = None) -> None:
        b, a = _default_ba(order if order is not None else DEFAULT_ORDER,
                           ripple_db if ripple_db is not None
                           else DEFAULT_RIPPLE_DB,
                           cutoff if cutoff is not None else DEFAULT_CUTOFF)
        a = np.asarray(a, np.float64)
        self._b = jnp.asarray(np.asarray(b, np.float64) / a[0],
                              jnp.float32)
        self._a = jnp.asarray(a / a[0], jnp.float32)
        self.reset()

    def reset(self) -> None:
        self._z = jnp.zeros((self._b.shape[0] - 1,), jnp.float32)

    def __call__(self, chunk: np.ndarray) -> np.ndarray:
        from .dtw import _chunk_bucket      # shared jit-shape bucketing

        x = np.asarray(chunk, np.float32).reshape(-1)
        c = x.shape[0]
        if c == 0:
            return np.zeros((0,), np.float32)
        cp = _chunk_bucket(c)
        xp = np.zeros((cp,), np.float32)
        xp[:c] = x
        y, self._z = _lfilter_scan_carry(self._b, self._a, jnp.asarray(xp),
                                         self._z, jnp.int32(c))
        # slice on the host: a device slice compiles once per tail length
        return np.asarray(y)[:c]


# ---------------------------------------------------------------------------
# The paper's pre-processing pipeline
# ---------------------------------------------------------------------------

#: Paper §3.1.1/§4: six-order low-pass Chebyshev filter.  Ripple/cutoff are
#: not stated in the paper; 1 dB ripple with cutoff at 0.125 Nyquist keeps
#: the multi-second phase structure of 1 Hz utilization traces while killing
#: sampling jitter.
DEFAULT_ORDER = 6
DEFAULT_RIPPLE_DB = 1.0
DEFAULT_CUTOFF = 0.125


@functools.lru_cache(maxsize=None)
def _default_ba(order: int, ripple_db: float, cutoff: float):
    return cheby1_design(order, ripple_db, cutoff)


def denoise(x: jax.Array, *, order: int = DEFAULT_ORDER,
            ripple_db: float = DEFAULT_RIPPLE_DB,
            cutoff: float = DEFAULT_CUTOFF, zero_phase: bool = True) -> jax.Array:
    """De-noise series (last axis) with the paper's Chebyshev low-pass."""
    b, a = _default_ba(order, ripple_db, cutoff)
    x = jnp.asarray(x, dtype=jnp.float32)
    return filtfilt(b, a, x) if zero_phase else lfilter(b, a, x)


def normalize01(x: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Magnitude normalization to [0, 1] (paper §3.1.1), per series."""
    lo = jnp.min(x, axis=-1, keepdims=True)
    hi = jnp.max(x, axis=-1, keepdims=True)
    return (x - lo) / jnp.maximum(hi - lo, eps)


def preprocess(x: jax.Array, **kw) -> jax.Array:
    """Full paper pre-processing: Chebyshev de-noise then [0,1] normalize."""
    return normalize01(denoise(x, **kw))


# ---------------------------------------------------------------------------
# Batched (padded-bank) pre-processing
# ---------------------------------------------------------------------------

def preprocess_bank(x, lengths, **kw) -> np.ndarray:
    """Paper pre-processing over a padded ``[K, M]`` bank, row-for-row
    **identical** to the scalar :func:`preprocess` of each unpadded series.

    ``filtfilt``'s backward pass is anti-causal, so filtering the padded
    rows directly would bleed the padding's edge transient back into the
    valid prefix — enough to flip 0.9-threshold match decisions on short
    series.  Instead rows are grouped by true length and each group is
    processed as one batch at its native length (reflection padding and
    normalization statistics see exactly the unpadded series), then
    re-packed with edge padding.  Dispatch count = number of distinct
    lengths — the parameter-set buckets real captures quantize into — not
    K.  Returns a float32 numpy array [K, M].
    """
    x = np.asarray(x, np.float32)
    lengths = np.asarray(lengths, np.int64).reshape(-1)
    out = np.empty_like(x)
    for l in np.unique(lengths):
        idx = np.nonzero(lengths == l)[0]
        block = np.asarray(preprocess(jnp.asarray(x[idx, :l]), **kw))
        out[idx, :l] = block
        out[idx, l:] = block[:, -1:]
    return out
