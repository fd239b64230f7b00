"""Shared layer primitives: norms, projections, rotary embeddings, MLPs,
embeddings and the loss. Parameters are plain nested dicts of jnp arrays so
everything composes with pjit/shard_map and ``jax.eval_shape``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig

__all__ = ["dense_init", "dense", "rmsnorm_init", "rmsnorm", "rope",
           "mrope", "mlp_init", "mlp", "embed_init", "embed", "unembed",
           "cross_entropy", "Dtypes"]


class Dtypes:
    @staticmethod
    def param(cfg: ModelConfig):
        return jnp.dtype(cfg.param_dtype)

    @staticmethod
    def compute(cfg: ModelConfig):
        return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# dense / norm
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = (1.0 / math.sqrt(d_in)) if scale is None else scale
    return {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
                  ).astype(dtype)}


def dense(p, x: jax.Array) -> jax.Array:
    return jnp.einsum("...d,df->...f", x, p["w"].astype(x.dtype))


def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: [..., S, H, hd]; positions: [..., S] int."""
    half = x.shape[-1] // 2
    freqs = _rope_freqs(x.shape[-1], theta)                  # [half]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1f * cos - x2f * sin,
                            x2f * cos + x1f * sin], axis=-1).astype(x.dtype)


def mrope(x: jax.Array, positions: jax.Array, sections: Tuple[int, ...],
          theta: float = 10000.0) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): ``positions`` is [3, ..., S] for the
    (temporal, height, width) ids; the head_dim/2 frequency channels are
    split into ``sections`` (summing to head_dim//2), each section rotated
    by its own position stream."""
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = _rope_freqs(x.shape[-1], theta)                  # [half]
    parts = []
    start = 0
    for s, sec in zip(positions, sections):
        parts.append(s[..., None].astype(jnp.float32) * freqs[start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)                    # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1f * cos - x2f * sin,
                            x2f * cos + x1f * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d_ff = d_ff or cfg.d_ff
    pd = Dtypes.param(cfg)
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], cfg.d_model, d_ff, pd),
         "w_down": dense_init(ks[1], d_ff, cfg.d_model, pd)}
    if cfg.act == "swiglu":
        p["w_gate"] = dense_init(ks[2], cfg.d_model, d_ff, pd)
    return p


def mlp(p, x: jax.Array, cfg: ModelConfig, shard=lambda x, k: x) -> jax.Array:
    up = shard(dense(p["w_up"], x), "ffn")
    if cfg.act == "swiglu":
        h = jax.nn.silu(shard(dense(p["w_gate"], x), "ffn")) * up
    else:
        h = jax.nn.gelu(up)
    return dense(p["w_down"], h)


# ---------------------------------------------------------------------------
# embeddings / loss
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig) -> Dict:
    pd = Dtypes.param(cfg)
    nb = max(cfg.num_codebooks, 1)
    ks = jax.random.split(key, 2)
    p = {"table": (jax.random.normal(ks[0], (nb * cfg.vocab_size, cfg.d_model),
                                     jnp.float32) * 0.02).astype(pd)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], cfg.d_model,
                                  nb * cfg.vocab_size, pd, scale=0.02)
    return p


def _take_rows(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` along axis 0 on any mesh.  Under explicit-sharding
    axes the gather needs its output sharding spelled out: it keeps the
    indices' sharding, then the table's trailing-dim sharding (the
    compiler brings in the collectives a vocab-sharded table needs)."""
    mesh = jax.typeof(table).sharding.mesh
    if jax.sharding.AxisType.Explicit not in mesh.axis_types:
        return jnp.take(table, idx, axis=0)
    ispec = tuple(jax.typeof(idx).sharding.spec)
    tspec = tuple(jax.typeof(table).sharding.spec)
    ispec += (None,) * (idx.ndim - len(ispec))
    tspec += (None,) * (table.ndim - len(tspec))
    used = {a for s in ispec if s is not None
            for a in (s if isinstance(s, tuple) else (s,))}
    trail = tuple(None if s is not None and set(
        s if isinstance(s, tuple) else (s,)) & used else s
        for s in tspec[1:])
    out = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*ispec, *trail))
    return table.at[idx].get(out_sharding=out)


def embed(p, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """tokens: [B, S] or [B, S, num_codebooks] -> [B, S, d] (codebooks sum)."""
    table = p["table"].astype(Dtypes.compute(cfg))
    if tokens.ndim == 3:                      # musicgen: per-codebook offset
        nb = tokens.shape[-1]
        offs = jnp.arange(nb, dtype=tokens.dtype) * cfg.vocab_size
        return _take_rows(table, tokens + offs).sum(axis=2)
    return _take_rows(table, tokens)


@jax.named_scope("unembed")
def unembed(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """-> [B, S, (nb*)vocab] logits."""
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, p["table"].astype(x.dtype))
    return dense(p["unembed"], x)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy; logits [..., V] (any leading dims)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
