import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with ShapeDtypeStruct stand-ins (no allocation), record
memory/cost analyses and the three roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-15b \
        --shape train_4k [--multi-pod] [--force]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json; the
roofline tables in EXPERIMENTS.md are generated from them.
"""

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import (ARCHS, SHAPES, canonical, cells, exec_default, get,
                       input_specs)
from ..core import hloparse
from ..core.hlocost import parse_module
from ..core.signatures import TPU_V5E
from ..models import model as model_lib
from ..models.config import ModelConfig
from ..sharding import rules
from ..train.optim import AdamWConfig, adamw_init
from ..train.step import make_train_step
from .mesh import make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _ns(specs_tree, mesh):
    """PartitionSpec tree -> NamedSharding tree (for out_shardings)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _sds_with(specs_tree, shape_tree, mesh):
    return jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        shape_tree, specs_tree)


def _apply_exec(cfg: ModelConfig, ex: rules.ExecConfig) -> ModelConfig:
    return dataclasses.replace(
        cfg, remat=ex.remat, attn_block_q=ex.attn_block_q,
        attn_block_kv=ex.attn_block_kv,
        blockwise_attn_threshold=getattr(ex, "blockwise_threshold", 4096),
        moe_expert_tp=getattr(ex, "moe_expert_tp", False))


def build_cell(arch: str, shape: str, mesh, ex: Optional[rules.ExecConfig] = None):
    """-> (jitted fn, arg ShapeDtypeStructs, meta dict)"""
    arch = canonical(arch)
    ex = ex or exec_default(arch, shape)
    cfg = _apply_exec(get(arch), ex)
    spec = SHAPES[shape]
    daxes = rules.logical_batch_axes(mesh)
    shard = rules.make_shard_fn(mesh, ex, spec.global_batch)

    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda k: model_lib.init(k, cfg), key)
    pspecs = rules.param_specs(params_shape, cfg, mesh, ex)
    params_sds = _sds_with(pspecs, params_shape, mesh)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params_shape))

    meta = {"arch": arch, "shape": shape, "exec": ex.as_dict(),
            "n_params": n_params, "mesh": dict(mesh.shape)}

    if spec.kind == "train":
        opt_cfg = AdamWConfig(moment_dtype=ex.optim_dtype)
        opt_shape = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_shape)
        ospecs_mv = rules.opt_state_specs(params_shape, pspecs, mesh, ex)
        ospecs = type(opt_shape)(count=P(), m=ospecs_mv, v=ospecs_mv)
        opt_sds = _sds_with(ospecs, opt_shape, mesh)

        batch_shape = input_specs(arch, shape, reduced=cfg)
        bspecs = rules.batch_specs(batch_shape, mesh)
        batch_sds = _sds_with(bspecs, batch_shape, mesh)

        step = make_train_step(cfg, ex, opt_cfg, mesh=mesh, data_axes=daxes,
                               shard=shard)
        fn = jax.jit(step, out_shardings=(_ns(pspecs, mesh), _ns(ospecs, mesh), None),
                     donate_argnums=(0, 1))
        args = (params_sds, opt_sds, batch_sds)
        meta["step"] = "train_step"
        return fn, args, meta

    # serving cells
    cache_shape = model_lib.make_cache(cfg, spec.global_batch, spec.seq_len)
    cspecs = rules.cache_specs(cache_shape, cfg, mesh, spec.global_batch)
    cache_sds = _sds_with(cspecs, cache_shape, mesh)
    io = input_specs(arch, shape, reduced=cfg)
    io_specs = rules.batch_specs(io, mesh)
    io_sds = _sds_with(io_specs, io, mesh)

    if spec.kind == "prefill":
        def prefill_step(params, tokens, cache, extra_embeds, positions):
            return model_lib.prefill(params, tokens, cache, cfg,
                                     extra_embeds=extra_embeds,
                                     positions=positions, mesh=mesh,
                                     data_axes=daxes, shard=shard)
        fn = jax.jit(prefill_step, donate_argnums=(2,),
                     out_shardings=(None, _ns(cspecs, mesh)))
        args = (params_sds, io_sds["tokens"], cache_sds,
                io_sds.get("extra_embeds"), io_sds.get("positions"))
        meta["step"] = "prefill_step"
        return fn, args, meta

    def serve_step(params, token, cache, pos):
        return model_lib.decode_step(params, token, cache, pos, cfg,
                                     mesh=mesh, data_axes=daxes, shard=shard)
    fn = jax.jit(serve_step, donate_argnums=(2,), out_shardings=(None, _ns(cspecs, mesh)))
    args = (params_sds, io_sds["token"], cache_sds, io_sds["pos"])
    meta["step"] = "serve_step"
    return fn, args, meta


def _cost_scalar(ca: Dict[str, Any], key: str) -> float:
    if not ca:
        return 0.0
    total = 0.0
    for k, v in ca.items():
        if k == key or k.startswith(key):
            try:
                total += float(v)
            except (TypeError, ValueError):
                pass
    return total


def _kernel_io_estimate(cfg: ModelConfig, shape: str, chips: int,
                        spec_kind: str) -> float:
    """Analytic HBM IO per chip of the Pallas flash-attention / GLA kernels
    replacing the tagged XLA interior traffic: each kernel invocation reads
    q,k,v(+gates) and writes o once; backward re-reads them and writes
    dq,dk,dv (~2.5x forward IO with recompute)."""
    spec = SHAPES[shape]
    if spec_kind == "decode":
        tokens = spec.global_batch
    else:
        tokens = spec.global_batch * spec.seq_len
    mult = 3.5 if spec_kind == "train" else 1.0     # fwd + bwd(re-read+grads)
    per_layer = 0.0
    kinds = cfg.layer_kinds()
    n_attn = sum(1 for k in kinds if "attn" in k or k == "shared_attn")
    n_gla = sum(1 for k in kinds if k in ("mamba2", "mlstm"))
    if cfg.attn_kind == "mla":
        width = cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                                 + cfg.v_head_dim)
    else:
        width = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    per_layer += n_attn * 4.0 * tokens * (width / 3.0) * 2  # q+k+v+o bf16
    d_inner = cfg.ssm_expand * cfg.d_model
    per_layer += n_gla * 4.0 * tokens * d_inner * 2
    return mult * per_layer / chips


def roofline(meta: Dict, cost: Dict, coll: Dict[str, float],
             spec_kind: str) -> Dict[str, Any]:
    chips = 1
    for v in meta["mesh"].values():
        chips *= v
    flops = _cost_scalar(cost, "flops")          # per-chip (partitioned HLO)
    nbytes = _cost_scalar(cost, "bytes accessed")
    coll_bytes = sum(coll.values())
    t_compute = flops / TPU_V5E.peak_flops
    t_memory = nbytes / TPU_V5E.hbm_bw
    t_coll = coll_bytes / TPU_V5E.ici_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    n = meta["n_params"]
    spec = SHAPES[meta["shape"]]
    tokens = spec.global_batch * (spec.seq_len if spec_kind != "decode" else 1)
    mult = 6.0 if spec_kind == "train" else 2.0
    n_active = meta.get("n_active_params", n)
    model_flops_global = mult * n_active * tokens
    model_flops_chip = model_flops_global / chips
    return {
        "chips": chips, "per_chip": {"flops": flops, "bytes": nbytes,
                                     "collective_bytes": coll_bytes},
        "terms_seconds": terms, "dominant": dominant,
        "model_flops_global": model_flops_global,
        "useful_compute_ratio": (model_flops_chip / flops) if flops else 0.0,
        "roofline_fraction": (model_flops_chip / TPU_V5E.peak_flops
                              / max(terms.values())) if max(terms.values()) else 0.0,
        "collective_breakdown": coll,
    }


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             ex: Optional[rules.ExecConfig] = None, out_dir: str = OUT_DIR,
             force: bool = False, tag: str = "") -> Dict[str, Any]:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        f"{canonical(arch)}__{shape}__{mesh_name}{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, meta = build_cell(arch, shape, mesh, ex)

    # active params for MoE useful-FLOPs accounting
    cfg = get(arch)
    if cfg.is_moe:
        key = jax.random.PRNGKey(0)
        pshape = jax.eval_shape(lambda k: model_lib.init(k, cfg), key)
        meta["n_active_params"] = _active_params_abstract(pshape, cfg)

    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_info = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_info[attr] = int(v)

    cost = {k: (float(v) if np.isscalar(v) else float(np.sum(v)))
            for k, v in (compiled.cost_analysis() or {}).items()
            if not isinstance(v, (dict, list))}

    hlo = compiled.as_text()
    mc = parse_module(hlo)          # trip-count-aware per-device cost model
    coll = mc.collective_bytes
    coll_counts = mc.collective_counts
    spec_kind = SHAPES[shape].kind
    rf = roofline(meta, {"flops": mc.flops, "bytes accessed": mc.bytes},
                  coll, spec_kind)
    rf["xla_cost_analysis_flops"] = cost.get("flops", 0.0)

    # kernel-adjusted memory term: the tagged flash_tile / gla_chunk
    # interior traffic is an XLA-CPU fusion-boundary artifact — on TPU the
    # Pallas kernels keep those tiles in VMEM; replace it with the
    # analytic kernel IO.
    interior = (mc.tag_bytes.get("flash_tile", 0.0)
                + mc.tag_bytes.get("gla_chunk", 0.0))
    cfg_full = _apply_exec(get(arch), ex or exec_default(arch, shape))
    kio = _kernel_io_estimate(cfg_full, shape, rf["chips"], spec_kind)
    adj_bytes = max(mc.bytes - interior, 0.0) + kio
    t_adj = adj_bytes / TPU_V5E.hbm_bw
    terms_adj = dict(rf["terms_seconds"], memory=t_adj)
    model_flops_chip = rf["model_flops_global"] / rf["chips"]
    rf["kernel_adjusted"] = {
        "interior_bytes_removed": interior,
        "kernel_io_bytes": kio,
        "memory_term_s": t_adj,
        "dominant": max(terms_adj, key=terms_adj.get),
        "roofline_fraction": (model_flops_chip / TPU_V5E.peak_flops
                              / max(terms_adj.values()))
        if max(terms_adj.values()) else 0.0,
    }

    record = {
        **meta, "mesh_name": mesh_name,
        "timing": {"lower_s": t_lower, "compile_s": t_compile},
        "memory_analysis": mem_info,
        "cost_analysis": {k: cost[k] for k in sorted(cost)[:20]},
        "collective_counts": coll_counts,
        "tag_flops": mc.tag_flops,
        "tag_bytes": mc.tag_bytes,
        "roofline": rf,
        "hlo_bytes": len(hlo),
    }
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    print(f"[dryrun] {arch} x {shape} x {mesh_name}: "
          f"dominant={rf['dominant']} "
          f"terms={ {k: f'{v:.3e}' for k, v in rf['terms_seconds'].items()} } "
          f"roofline_frac={rf['roofline_fraction']:.3f} "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return record


def _active_params_abstract(pshape, cfg: ModelConfig) -> int:
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(pshape))
    routed = 0
    for seg in pshape["segments"]:
        for name, blk in seg.items():
            if "moe" in blk:
                routed += sum(int(np.prod(x.shape))
                              for x in jax.tree.leaves(blk["moe"]["experts"]))
    return int(total - routed + routed * cfg.top_k / cfg.num_experts)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep all cells on both meshes")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--exec-json", default=None,
                    help="JSON dict of ExecConfig overrides")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    ex = None
    if args.exec_json:
        base = exec_default(args.arch, args.shape).as_dict() \
            if args.arch else {}
        base.update(json.loads(args.exec_json))
        ex = rules.ExecConfig.from_dict(base)

    if args.all:
        failures = []
        for arch, shape, _skip in cells():
            for mp in (False, True):
                try:
                    run_cell(arch, shape, multi_pod=mp, force=args.force,
                             tag=args.tag)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mp, repr(e)[:200]))
                    print(f"[dryrun] FAIL {arch} x {shape} mp={mp}: {e!r}")
        if failures:
            raise SystemExit(f"{len(failures)} cells failed: {failures}")
        print("[dryrun] all cells OK")
        return

    assert args.arch and args.shape, "--arch and --shape (or --all) required"
    run_cell(args.arch, args.shape, multi_pod=args.multi_pod, ex=ex,
             force=args.force, tag=args.tag)


if __name__ == "__main__":
    main()
