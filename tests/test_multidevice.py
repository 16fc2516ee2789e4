"""Multi-device semantics, validated in subprocesses with
``--xla_force_host_platform_device_count=8`` (the main test process keeps the
real 1-device view; forcing devices is process-global).

Covers: sharded train step on a 2x4 mesh, sequence-parallel shard_map
attention == single-device blocked attention, int8-compressed DP psum ==
plain mean, and GPipe pipeline_fwd == sequential block application.
"""
import os
import pathlib
import subprocess
import sys
import textwrap


SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_child(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_runs_2x4():
    run_child("""
        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh
        from repro.models import lm
        from repro.optim.adamw import OptimizerConfig, init_opt_state
        from repro.parallel import sharding as shd
        from repro.train.steps import make_train_step

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        jax.set_mesh(mesh)
        cfg = get_config("llama3.2-1b").smoke()
        params = lm.init_model(cfg, jax.random.PRNGKey(0))
        pspecs = lm.model_specs(cfg)
        psh = shd.param_shardings(pspecs, cfg, mesh)
        params = jax.tree.map(jax.device_put, params, psh)
        opt = OptimizerConfig(peak_lr=1e-3, total_steps=4, warmup_steps=1)
        state = init_opt_state(params, opt)
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32) + 3,
                 "targets": jnp.ones((4, 32), jnp.int32)}
        bsh = NamedSharding(mesh, P(("data",), None))
        batch = {k: jax.device_put(v, bsh) for k, v in batch.items()}
        step = jax.jit(make_train_step(cfg, opt))
        p2, s2, m = step(params, state, batch)
        assert jnp.isfinite(m["loss"]), m
        print("loss", float(m["loss"]))
    """)


def test_seq_dp_attention_matches_single_device():
    run_child("""
        import dataclasses
        from repro.configs import get_config
        from repro.models import attention
        from repro.models.common import init_params

        cfg = get_config("llama3.2-1b").smoke()
        cfg_sp = dataclasses.replace(cfg, shard_strategy="seq_dp")
        b, s = 2, 64
        params = init_params(attention.attention_specs(cfg),
                             jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model),
                              jnp.float32)
        ref = attention.attention_fwd(params, x, cfg, causal=True)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        jax.set_mesh(mesh)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
        ps = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(mesh, P())), params)
        out = jax.jit(lambda p, h: attention.attention_fwd(
            p, h, cfg_sp, causal=True))(ps, xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        print("seq_dp == ref OK")
    """)


def test_compressed_psum_matches_mean():
    run_child("""
        from repro.optim.compression import make_compressed_psum

        mesh = jax.make_mesh((8,), ("data",))
        jax.set_mesh(mesh)
        from jax.experimental.shard_map import shard_map
        rng = np.random.default_rng(0)
        # one distinct gradient per shard: global view stacked on axis 0
        g_all = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))

        def local(g):
            gf = g[0]
            scale = jnp.maximum(jax.lax.pmax(jnp.max(jnp.abs(gf)), "data"),
                                1e-12) / 127.0
            q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
            qs = jax.lax.psum(q.astype(jnp.int32), "data")
            return ((qs.astype(jnp.float32) * scale) / 8)[None]

        f = shard_map(local, mesh=mesh, in_specs=(P("data", None),),
                      out_specs=P("data", None), check_rep=False)
        out = f(g_all)
        mean_true = np.asarray(g_all).mean(0)
        # every shard's output approximates the true mean within quant error
        np.testing.assert_allclose(np.asarray(out)[0], mean_true,
                                   atol=np.abs(np.asarray(g_all)).max() / 64)
        print("compressed psum OK")
    """)


def test_pipeline_fwd_matches_sequential():
    run_child("""
        from repro.parallel.pipeline import pipeline_fwd

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        jax.set_mesh(mesh)
        rng = np.random.default_rng(0)
        n_blocks, d = 4, 16
        w = jnp.asarray(rng.normal(size=(n_blocks, d, d)).astype(np.float32)
                        / np.sqrt(d))
        h = jnp.asarray(rng.normal(size=(8, 4, d)).astype(np.float32))

        def block_apply(stage_w, hm):
            for i in range(stage_w.shape[0]):
                hm = jnp.tanh(hm @ stage_w[i])
            return hm

        out = pipeline_fwd(block_apply, w, h, mesh, n_microbatches=4,
                           axis="pod")
        ref = h
        for i in range(n_blocks):
            ref = jnp.tanh(ref @ w[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        print("pipeline OK")
    """)
