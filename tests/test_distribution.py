"""Distribution correctness on multi-device host platforms.

These run in subprocesses because the forced host device count must be set
before JAX initializes (same constraint as launch/dryrun.py).
"""
import subprocess
import sys
import textwrap



def _run(script: str, devices: int = 8, timeout: int = 420):
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os
    env["PATH"] = os.environ.get("PATH", env["PATH"])
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=".")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_cp_decode_matches_plain():
    """Context-parallel (shard_map) decode == single-device plain decode."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.models import cpu_context, init_params, init_cache, prefill, decode_step
        from repro.models.parallel import ParallelContext

        cfg = get_config('gemma-2b').reduced()       # MQA: kv=1 -> CP path
        key = jax.random.key(0)
        params = init_params(key, cfg)
        B, S = 4, 16
        toks = jax.random.randint(key, (B, S + 4), 0, cfg.vocab_size)

        # reference: plain single-device decode
        ctx0 = cpu_context()
        cache = init_cache(cfg, B, 32)
        last0, cache0 = prefill(params, {'tokens': toks[:, :S]}, cache,
                                cfg=cfg, ctx=ctx0)
        l0, _ = decode_step(params, toks[:, S:S+1], cache0, jnp.int32(S),
                            cfg=cfg, ctx=ctx0)

        # CP: 2 data x 4 model; cache seq 32 % 4 == 0, kv_heads=1 % 4 != 0
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ('data', 'model'))
        ctx = ParallelContext(mesh=mesh, batch_axes=('data',),
                              model_axis='model')
        from repro.models.layers import kv_cache_cp
        assert kv_cache_cp(cfg.n_kv_heads, 32, ctx)
        cache = init_cache(cfg, B, 32)
        last1, cache1 = prefill(params, {'tokens': toks[:, :S]}, cache,
                                cfg=cfg, ctx=ctx)
        l1, _ = jax.jit(lambda p, t, c, pos: decode_step(
            p, t, c, pos, cfg=cfg, ctx=ctx))(params, toks[:, S:S+1],
                                             cache1, jnp.int32(S))
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   rtol=3e-2, atol=3e-2)
        print('CP decode OK')
    """)
    assert "CP decode OK" in out


def test_moe_ep_a2a_matches_dense():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.moe import init_moe, moe_layer, moe_layer_ep_a2a
        from repro.models.parallel import ParallelContext, cpu_context

        cfg = get_config('deepseek-moe-16b').reduced()   # 4 experts, top-2
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ('data', 'model'))
        ctx = ParallelContext(mesh=mesh, batch_axes=('data',),
                              model_axis='model')
        key = jax.random.key(0)
        p = init_moe(key, cfg)
        x = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 32, cfg.d_model), jnp.float32)
        o1, _ = moe_layer(p, x, cfg=cfg, ctx=cpu_context())
        o2, _ = jax.jit(lambda p, x: moe_layer_ep_a2a(
            p, x, cfg=cfg, ctx=ctx, capacity_factor=8.0))(p, x)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=3e-3, atol=3e-3)
        # gradients flow through the a2a
        g = jax.grad(lambda p, x: jnp.sum(moe_layer_ep_a2a(
            p, x, cfg=cfg, ctx=ctx, capacity_factor=8.0)[0] ** 2))(p, x)
        assert all(bool(jnp.isfinite(leaf).all()) for leaf in jax.tree.leaves(g))
        print('ep_a2a OK')
    """)
    assert "ep_a2a OK" in out


def test_sharded_train_step_runs():
    """A real (tiny) sharded train step executes on an 8-device mesh."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import init_params, dummy_batch, params_shapes
        from repro.models.parallel import ParallelContext, param_shardings
        from repro.training import AdamWConfig, init_opt_state, make_train_step

        cfg = get_config('gemma-2b').reduced(n_layers=2, d_model=128,
                                             vocab_size=512)
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ('data', 'model'))
        ctx = ParallelContext(mesh=mesh, batch_axes=('data',),
                              model_axis='model')
        params = init_params(jax.random.key(0), cfg)
        pshard = param_shardings(params_shapes(cfg), ctx)
        params = jax.device_put(params, pshard)
        opt = init_opt_state(params)
        step = jax.jit(make_train_step(cfg, ctx, AdamWConfig(warmup_steps=1)))
        batch = dummy_batch(jax.random.key(1), cfg, 4, 32, 'train')
        batch = jax.device_put(batch, NamedSharding(mesh, P('data', None)))
        params, opt, metrics = step(params, opt, batch)
        assert bool(jnp.isfinite(metrics['loss']))
        print('sharded train OK', float(metrics['loss']))
    """)
    assert "sharded train OK" in out


def test_moe_capacity_matches_dense_cpu():
    from repro.configs import get_config
    from repro.models.moe import init_moe, moe_layer, moe_layer_capacity
    from repro.models.parallel import cpu_context
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = get_config("mixtral-8x7b").reduced()
    ctx = cpu_context()
    key = jax.random.key(0)
    p = init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model),
                          jnp.float32)
    o1, _ = moe_layer(p, x, cfg=cfg, ctx=ctx)
    o2, _ = moe_layer_capacity(p, x, cfg=cfg, ctx=ctx, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-3,
                               atol=2e-3)
