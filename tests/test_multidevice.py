"""Multi-device semantics (8 fake CPU devices in a subprocess so the main
test process keeps its single real device): MoE EP equivalence and
sharded train-step numerics vs single-device."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import ModelConfig
    from repro.models.moe import moe_init, moe_apply

    # capacity_factor=8 -> dropless: the local path computes per-expert
    # capacity from the full token set, EP shards compute it from their
    # local shard, so with tight capacity the *dropped* tokens differ by
    # design; dropless makes the two paths exactly comparable.
    cfg = ModelConfig(name="m", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=64, num_experts=8,
                      top_k=2, d_ff_expert=16, capacity_factor=8.0,
                      param_dtype="float32", dtype="float32")
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

    out_local, aux_local = moe_apply(p, x, cfg)                # 1-device path

    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((2, 4), ("data", "model"))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    out_ep, aux_ep = jax.jit(
        lambda p, x: moe_apply(p, x, cfg, mesh=mesh, data_axes=("data",))
    )(p, xs)
    err = float(np.max(np.abs(np.asarray(out_ep) - np.asarray(out_local))))
    aux_err = abs(float(aux_ep) - float(aux_local))
    assert err < 2e-4, f"EP vs local mismatch {err}"
    # aux balance stats are computed per data shard then averaged, which
    # differs from global-token stats at O(1/T_local) — approximation, not
    # a bug (routing itself is exact, as the output check above proves)
    assert aux_err < 1e-2, f"aux mismatch {aux_err}"
    print("MOE_EP_OK", err, aux_err)

    # sharded vs single-device train-step numerics
    from repro.models import model as model_lib
    from repro.sharding import rules
    from repro.train.optim import AdamWConfig, adamw_init
    from repro.train.step import make_train_step
    cfg2 = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                       num_kv_heads=2, d_ff=128, vocab_size=128,
                       param_dtype="float32", dtype="float32")
    params = model_lib.init(jax.random.PRNGKey(0), cfg2)
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params, opt_cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 128, size=(8, 32)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}

    p1, _, m1 = jax.jit(make_train_step(cfg2, rules.ExecConfig(), opt_cfg))(
        params, opt, batch)

    ex = rules.ExecConfig()
    pshape = jax.eval_shape(lambda k: model_lib.init(k, cfg2),
                            jax.random.PRNGKey(0))
    pspecs = rules.param_specs(pshape, cfg2, mesh, ex)
    shard_fn = rules.make_shard_fn(mesh, ex, 8)
    step = make_train_step(cfg2, ex, opt_cfg, mesh=mesh,
                           data_axes=("data",), shard=shard_fn)
    params_sh = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P)))
    bspecs = rules.batch_specs(batch, mesh)
    batch_sh = jax.device_put(batch, jax.tree.map(
        lambda s: NamedSharding(mesh, s), bspecs,
        is_leaf=lambda x: isinstance(x, P)))
    p2, _, m2 = jax.jit(step)(params_sh, opt, batch_sh)
    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    assert dl < 1e-4, f"loss mismatch {dl}"
    diffs = jax.tree.map(lambda a, b: float(np.max(np.abs(
        np.asarray(a) - np.asarray(b)))), p1, p2)
    md = max(jax.tree.leaves(diffs))
    assert md < 1e-4, f"param mismatch {md}"
    print("SHARDED_STEP_OK", dl, md)

    # the embedding gather also runs on jax.make_mesh's default
    # Explicit-axes mesh: vocab-sharded table, batch-sharded tokens.
    from repro.models.layers import embed
    emesh = jax.make_mesh((2, 4), ("data", "model"))
    table = jax.random.normal(jax.random.PRNGKey(2), (128, 64))
    want = np.asarray(embed({"table": table}, batch["tokens"], cfg2))
    got = jax.jit(lambda p, t: embed(p, t, cfg2))(
        {"table": jax.device_put(table, NamedSharding(emesh, P("model", None)))},
        jax.device_put(batch["tokens"], NamedSharding(emesh, P("data", None))))
    assert np.array_equal(np.asarray(got), want), "explicit-mesh embed"
    print("EXPLICIT_EMBED_OK")
""")


@pytest.mark.slow
def test_multidevice_semantics():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=os.path.join(
        os.path.dirname(__file__), ".."), env=env, capture_output=True,
        text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MOE_EP_OK" in r.stdout and "SHARDED_STEP_OK" in r.stdout
    assert "EXPLICIT_EMBED_OK" in r.stdout
