"""Mixture-of-Experts FFN with expert parallelism.

Design (production path): experts are sharded over the ``model`` mesh axis
(EP composes with the Megatron-TP layout because activations are
replicated across ``model`` between blocks).  Inside a ``shard_map`` region
each device:

  1. computes router probabilities for its local tokens (router weights are
     replicated — redundant routing, no all-to-all for the gate);
  2. builds a capacity-bounded dispatch index for **its own experts only**
     (one-hot + cumsum position-in-expert, tokens over capacity drop);
  3. gathers tokens into a dense [E_local, C, D] buffer, runs the expert
     GEMMs, and scatters weighted outputs back to token order;
  4. ``psum`` over ``model`` combines contributions from all expert shards
     (same collective pattern as the TP row-parallel matmul it replaces).

This avoids the O(T*E*C) dispatch einsum entirely — at 384 experts that
tensor would be ~10^2 GB/device — while keeping every op a static-shape
gather/scatter that GSPMD lowers on any backend.  The identical local
function runs unmapped when no mesh is given (smoke tests / 1 device).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import Dtypes, dense_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig) -> Dict:
    pd = Dtypes.param(cfg)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(D)
    p = {
        "router": dense_init(ks[0], D, E, pd, scale=0.02),
        "experts": {
            "w_gate": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * scale).astype(pd),
            "w_up": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * scale).astype(pd),
            "w_down": (jax.random.normal(ks[3], (E, F, D), jnp.float32)
                       * (1.0 / math.sqrt(F))).astype(pd),
        },
    }
    if cfg.num_shared_experts:
        Fs = cfg.d_ff_expert * cfg.num_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": dense_init(kss[0], D, Fs, pd),
                       "w_up": dense_init(kss[1], D, Fs, pd),
                       "w_down": dense_init(kss[2], Fs, D, pd)}
    return p


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts))
    return max(4, c)


@jax.named_scope("moe_local")
def _moe_local(x2d, router_w, wg, wu, wd, cfg: ModelConfig,
               e_offset: jax.Array, axis: Optional[str]):
    """Per-device MoE over local experts.  x2d: [T, D] (local tokens);
    wg/wu/wd: local expert slices [E_loc, ...]; ``e_offset`` = first global
    expert id owned here."""
    T, D = x2d.shape
    E, K = cfg.num_experts, cfg.top_k
    E_loc = wg.shape[0]
    C = _capacity(T, cfg)

    logits = jnp.einsum("td,de->te", x2d, router_w.astype(x2d.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                      # [T, K]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (computed on global stats; identical on all
    # model shards since routing is redundant)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    # local expert ids in [0, E_loc); invalid -> E_loc (sentinel)
    le = top_e - e_offset                                        # [T, K]
    valid = (le >= 0) & (le < E_loc)
    le = jnp.where(valid, le, E_loc)

    # position of each (t, k) within its expert, counted in flat (t*K+k) order
    onehot = jax.nn.one_hot(le.reshape(-1), E_loc + 1, dtype=jnp.int32)  # [T*K, E+1]
    pos = jnp.cumsum(onehot, axis=0) - onehot                    # exclusive
    pos = (pos * onehot).sum(-1)                                 # [T*K]
    flat_le = le.reshape(-1)
    keep = (flat_le < E_loc) & (pos < C)

    # dispatch: slot -> token index (sentinel T => zero row)
    slot = jnp.where(keep, flat_le * C + pos, E_loc * C)
    tok_idx = jnp.repeat(jnp.arange(T), K)
    slot_to_tok = jnp.full((E_loc * C + 1,), T, jnp.int32).at[slot].set(
        tok_idx.astype(jnp.int32), mode="drop")
    xpad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    xe = xpad[slot_to_tok[:-1]].reshape(E_loc, C, D)

    # expert GEMMs
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype)))
    ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(xe.dtype))      # [E_loc, C, D]

    # combine: scatter-add weighted expert outputs back to token order.
    # This stays capacity-sized ([E_loc*C, D]) — the gather formulation
    # materializes [T*K, D] (15 GB f32 per layer on kimi train_4k).
    w_slot = jnp.zeros((E_loc * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, top_p.reshape(-1), 0.0), mode="drop")[:-1]
    contrib = ye.reshape(E_loc * C, D) * w_slot[:, None].astype(ye.dtype)
    out = jnp.zeros((T + 1, D), x2d.dtype).at[slot_to_tok[:-1]].add(
        contrib.astype(x2d.dtype), mode="drop")[:T]

    if axis is not None:
        out = jax.lax.psum(out, axis)
    return out, aux


def moe_apply(p, x: jax.Array, cfg: ModelConfig,
              mesh: Optional[jax.sharding.Mesh] = None,
              data_axes: Tuple[str, ...] = ("data",),
              model_axis: str = "model",
              expert_tp: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    B, S, D = x.shape
    router_w = p["router"]["w"]
    ex = p["experts"]

    def run(x_loc, rw, wg, wu, wd, e_offset, axis):
        out, aux = _moe_local(x_loc.reshape(-1, D), rw, wg, wu, wd, cfg,
                              e_offset, axis)
        return out.reshape(x_loc.shape), aux

    if mesh is None or mesh.shape.get(model_axis, 1) == 1 or \
            cfg.num_experts % max(mesh.shape.get(model_axis, 1), 1) != 0:
        out, aux = run(x, router_w, ex["w_gate"], ex["w_up"], ex["w_down"],
                       jnp.int32(0), None)
    elif expert_tp:
        # Serving mode: experts over "model", expert FFN dim over the data
        # axes, tokens REPLICATED over the mesh (decode batches are tiny).
        # No weight collectives at all; one psum of [T, D] combines both
        # the F-partials (data) and non-local experts (model).
        ep = mesh.shape[model_axis]
        all_axes = tuple(data_axes) + (model_axis,)

        dp = 1
        for a in data_axes:
            dp *= mesh.shape[a]

        def mapped_tp(x_loc, rw, wg, wu, wd):
            idx = jax.lax.axis_index(model_axis)
            e_off = idx * (cfg.num_experts // ep)
            out, aux = run(x_loc, rw, wg, wu, wd, e_off, all_axes)
            # return only this device's batch slice so the residual stream
            # stays batch-sharded (a replicated output forces the next
            # layer's attention to all-gather the KV cache — measured
            # 3.2e10 B/chip/layer on deepseek decode_32k)
            if B % dp == 0:
                di = jax.lax.axis_index(data_axes)
                out = jax.lax.dynamic_slice_in_dim(out, di * (B // dp),
                                                   B // dp, axis=0)
            return out, jax.lax.pmean(aux, all_axes)

        out_spec = P(data_axes, None, None) if B % dp == 0 \
            else P(None, None, None)
        out, aux = jax.shard_map(
            mapped_tp, mesh=mesh, check_vma=False,
            in_specs=(P(None, None, None), P(None, None),
                      P(model_axis, None, data_axes),
                      P(model_axis, None, data_axes),
                      P(model_axis, data_axes, None)),
            out_specs=(out_spec, P()),
        )(x, router_w, ex["w_gate"], ex["w_up"], ex["w_down"])
        aux = aux.mean() if aux.ndim else aux
    else:
        ep = mesh.shape[model_axis]

        all_axes = tuple(data_axes) + (model_axis,)

        def mapped(x_loc, rw, wg, wu, wd):
            idx = jax.lax.axis_index(model_axis)
            e_off = idx * (cfg.num_experts // ep)
            out, aux = run(x_loc, rw, wg, wu, wd, e_off, model_axis)
            return out, jax.lax.pmean(aux, all_axes)

        out, aux = jax.shard_map(
            mapped, mesh=mesh, check_vma=False,
            in_specs=(P(data_axes, None, None), P(None, None),
                      P(model_axis, None, None), P(model_axis, None, None),
                      P(model_axis, None, None)),
            out_specs=(P(data_axes, None, None), P()),
        )(x, router_w, ex["w_gate"], ex["w_up"], ex["w_down"])
        aux = aux.mean() if aux.ndim else aux

    if cfg.num_shared_experts:
        sh = p["shared"]
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sh["w_gate"]["w"].astype(x.dtype)))
        h = h * jnp.einsum("bsd,df->bsf", x, sh["w_up"]["w"].astype(x.dtype))
        out = out + jnp.einsum("bsf,fd->bsd", h, sh["w_down"]["w"].astype(x.dtype))
    return out, aux
