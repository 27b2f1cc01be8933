"""Distribution layer tests (multi host-device runs in subprocesses so the
main pytest process keeps a single CPU device)."""
import numpy as np


def test_pipeline_parallel_matches_sequential(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed import pipeline as pp
        mesh = make_mesh((4,), ('pipe',))
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) / 4
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
        stage = lambda w, x: jnp.tanh(x @ w)
        y = pp.pipeline_apply(stage, mesh, 'pipe', ws, x)
        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ ws[s])
        print('ERR', float(jnp.abs(y - ref).max()))
    """)
    assert float(out.split("ERR")[1]) < 1e-5


def test_compressed_allreduce_accuracy(subproc):
    out = subproc("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.distributed import compression as comp
        mesh = make_mesh((8,), ('data',))
        params = {'w': jax.random.normal(jax.random.PRNGKey(2), (16, 4))}
        xb = jax.random.normal(jax.random.PRNGKey(3), (32, 16))
        yb = jax.random.normal(jax.random.PRNGKey(4), (32, 4))
        loss = lambda p, b: jnp.mean((b['x'] @ p['w'] - b['y'])**2)
        batch = {'x': xb, 'y': yb}
        exact = jax.grad(lambda p: loss(p, batch))(params)
        gf = comp.make_compressed_dp_grad_fn(
            loss, mesh, ('data',),
            {'x': P('data', None), 'y': P('data', None)})
        with jax.set_mesh(mesh):
            approx = jax.jit(gf)(params, batch)
        rel = float(jnp.abs(approx['w'] - exact['w']).max()
                    / jnp.abs(exact['w']).max())
        print('REL', rel)
    """)
    assert float(out.split("REL")[1]) < 0.05


def test_ep_moe_matches_ragged(subproc):
    out = subproc("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import REGISTRY
        from repro.models import params as P, moe as MoE
        from repro.distributed import context as dist_ctx
        from repro.launch.mesh import make_mesh
        cfg = REGISTRY['deepseek-moe-16b'].reduced()
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_experts=8, top_k=2, impl='ragged'))
        pr = P.init_params(jax.random.PRNGKey(0), cfg)
        moe_p = jax.tree.map(lambda x: x[0], pr['layers'][0])['moe']
        x = jax.random.normal(jax.random.PRNGKey(5), (32, cfg.d_model))
        yr, auxr = MoE.moe_ragged(moe_p, cfg, x)
        mesh = make_mesh((4, 2), ('data', 'model'))
        cfg_ep = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, impl='ep', capacity_factor=4.0))
        ctx = dist_ctx.ParallelContext(
            mesh=mesh, batch_axes=('data',), model_axis='model',
            ep_axes=('data',))
        with dist_ctx.use(ctx), jax.set_mesh(mesh):
            yep, auxep = jax.jit(
                lambda p, x: MoE.moe_ep(p, cfg_ep, x))(moe_p, x)
        print('ERR', float(jnp.abs(yep - yr).max()))
        print('AUXERR', abs(float(auxep) - float(auxr)))
    """)
    assert float(out.split("ERR")[1].split()[0]) < 1e-4
    # the EP aux loss is a per-shard estimator of the global balance loss
    # (mean of local f_e*P_e products), not bit-identical to it
    assert float(out.split("AUXERR")[1]) < 0.3


def test_decode_tp_expanded_cache(subproc):
    """KV heads that do not divide the model axis are stored expanded to
    every query head; decode stores each token into that stacked cache
    and gives the logits and K/V of the unsharded step."""
    out = subproc("""
        import jax, jax.numpy as jnp
        from repro.configs import REGISTRY
        from repro.models import model as M, params as P
        from repro.distributed import context as dist_ctx
        from repro.launch.mesh import make_mesh
        cfg = REGISTRY['qwen3-0.6b'].reduced(n_kv_heads=1)
        pr = P.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                  cfg.vocab_size)

        def serve():
            _, cache = M.prefill(pr, cfg, tokens=toks[:, :3], cache_len=8)
            step = jax.jit(lambda c, t: M.decode_step(pr, cfg, c, tokens=t))
            lgs = []
            for t in range(3, 6):
                lg, cache = step(cache, toks[:, t])
                lgs.append(lg)
            return jnp.stack(lgs), cache

        ref_lg, ref_cache = serve()
        mesh = make_mesh((2, 2), ('data', 'model'))
        ctx = dist_ctx.ParallelContext(mesh=mesh, batch_axes=('data',),
                                       model_axis='model')
        with dist_ctx.use(ctx), jax.set_mesh(mesh):
            lg, cache = serve()
        k, ref_k = cache['layers'][0]['k'], ref_cache['layers'][0]['k']
        print('HEADS', k.shape[3], ref_k.shape[3])
        print('LGERR', float(jnp.abs(lg - ref_lg).max()))
        print('KERR', float(jnp.abs(k - jnp.repeat(ref_k, 4, axis=3)).max()))
    """)
    assert out.split("HEADS")[1].split()[:2] == ["4", "1"]
    assert float(out.split("LGERR")[1].split()[0]) < 1e-4
    assert float(out.split("KERR")[1].split()[0]) < 1e-5


def test_moe_gather_matches_dense():
    import jax
    from repro.configs import REGISTRY
    from repro.models import moe as MoE
    from repro.models import params as P
    cfg = REGISTRY["jamba-v0.1-52b"].reduced()
    pr = P.init_params(jax.random.PRNGKey(0), cfg)
    moe_p = jax.tree.map(lambda x: x[0], pr["layers"][1])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (4, cfg.d_model))
    yd, _ = MoE.moe_dense(moe_p, cfg, x)
    yg, _ = MoE.moe_gather(moe_p, cfg, x)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yg),
                               rtol=2e-4, atol=2e-4)


def test_sharding_rules_divisibility_fallback():
    """Non-divisible dims fall back instead of producing invalid specs."""
    from repro.configs import get_config
    from repro.distributed.sharding import param_spec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    # starcoder2: 36 heads don't divide 16 -> heads unsharded
    cfg = get_config("starcoder2-7b")
    spec = param_spec(("layers", "attn", "wq"), (1, 4608, 36, 128), cfg,
                      FakeMesh(), "train")
    assert spec[2] is None
    # gemma: 16 heads divide -> sharded over model
    cfg = get_config("gemma-7b")
    spec = param_spec(("layers", "attn", "wq"), (1, 3072, 16, 256), cfg,
                      FakeMesh(), "train")
    assert spec[2] == ("model",) or spec[2] == "model"
