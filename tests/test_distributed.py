"""Multi-device correctness: EP MoE, flash-decode, sharded train step.

These run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
(jax pins the device count at first init, and the main pytest process
must keep seeing 1 device for the CPU smoke tests).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str) -> str:
    script = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"\n'
        + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=560,
        cwd=REPO,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_moe_ep_matches_dense():
    """Expert-parallel shard_map path == dense oracle (ample capacity)."""
    run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import AxisType
        from repro.configs.base import ArchConfig
        from repro.models import moe
        from repro.models.context import ParallelCtx

        cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=32,
                         n_heads=4, n_kv_heads=4, d_ff=64, vocab=64, head_dim=8,
                         n_experts=8, topk=2, dtype_str="float32",
                         moe_capacity_factor=8.0)  # no drops -> exact match
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
        k = jax.random.PRNGKey(0)
        ks = jax.random.split(k, 5)
        p = {
            "router": jax.random.normal(ks[0], (32, 8)) * 0.5,
            "we1": jax.random.normal(ks[1], (8, 32, 64)) * 0.1,
            "we3": jax.random.normal(ks[2], (8, 32, 64)) * 0.1,
            "we2": jax.random.normal(ks[3], (8, 64, 32)) * 0.1,
        }
        x = jax.random.normal(ks[4], (64, 32))
        dense = moe.moe_dense(p, x, cfg)
        with mesh:
            ep = jax.jit(lambda pp, xx: moe.moe_ep(pp, xx, cfg, pctx))(p, x)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ep), rtol=2e-4, atol=2e-4)
        print("EP==dense OK")
        """
    )


def test_flash_decode_matches_dot():
    """shard_map flash-decoding == plain cache attention."""
    run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, math
        from jax.sharding import AxisType
        from repro.models.context import ParallelCtx
        from repro.models.flash_decode import flash_decode_attention
        from repro.models.layers import attention_dot, repeat_kv

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                           flash_decode=True)
        k = jax.random.PRNGKey(1)
        b, smax, h, kv, hd = 4, 64, 8, 2, 16
        q = jax.random.normal(k, (b, 1, h, hd))
        ck = jax.random.normal(jax.random.PRNGKey(2), (b, smax, kv, hd))
        cv = jax.random.normal(jax.random.PRNGKey(3), (b, smax, kv, hd))
        pos = jnp.int32(37)
        with mesh:
            got = jax.jit(lambda *a: flash_decode_attention(*a, pctx=pctx))(q, ck, cv, pos)
        want = attention_dot(q, repeat_kv(ck, h // kv), repeat_kv(cv, h // kv),
                             causal=True, q_offset=pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
        # windowed variant
        with mesh:
            got_w = jax.jit(lambda *a: flash_decode_attention(*a, pctx=pctx, window=16))(q, ck, cv, pos)
        want_w = attention_dot(q, repeat_kv(ck, h // kv), repeat_kv(cv, h // kv),
                               causal=True, window=16, q_offset=pos)
        np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w), rtol=2e-4, atol=2e-4)
        print("flash==dot OK")
        """
    )


def test_sharded_train_step_matches_single_device():
    """pjit'd train step on a 2x4 mesh == unsharded step (same batch)."""
    run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs.base import ArchConfig
        from repro.models import get_model
        from repro.optim import adamw
        from repro.runtime.train_loop import TrainSetup, make_train_step, jit_train_step, abstract_state
        from repro.data.pipeline import LmDataset, shard_batch
        from repro.runtime import sharding as shr

        cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                         head_dim=16, dtype_str="float32")
        api = get_model(cfg)
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ds = LmDataset(cfg, seq_len=32, batch=8, seed=0)
        np_batch = ds.np_batch(0)

        params = api.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        ref_step = make_train_step(TrainSetup(cfg=cfg, mesh=None), api)
        _, _, _, m_ref = ref_step(params, opt, None,
                                  {k: jnp.asarray(v) for k, v in np_batch.items()})

        setup = TrainSetup(cfg=cfg, mesh=mesh)
        abatch = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), np_batch)
        step = jit_train_step(setup, api, abatch)
        aparams, aopt = abstract_state(setup, api)
        from repro.runtime.train_loop import state_shardings
        pspecs, ospecs = state_shardings(setup, aparams, aopt)
        with mesh:
            p2 = jax.device_put(params, shr.named(mesh, pspecs))
            o2 = jax.device_put(opt, shr.named(mesh, ospecs))
            bspecs = shr.input_specs_tree(abatch, mesh)
            b2 = shard_batch(np_batch, mesh, bspecs)
            _, _, _, m_sh = step(p2, o2, None, b2)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]), rtol=2e-5)
        np.testing.assert_allclose(float(m_ref["gnorm"]), float(m_sh["gnorm"]), rtol=2e-4)
        print("sharded==single OK")
        """
    )


def test_elastic_mesh_choices():
    from repro.runtime.elastic import choose_mesh_shape

    # full pod, one dead host (8 devices lost), tiny salvage
    assert choose_mesh_shape(256, 16) == ((16, 16), ("data", "model"))
    shape, axes = choose_mesh_shape(248, 16)  # 248 = 8*31
    assert np.prod(shape) == 248
    shape, axes = choose_mesh_shape(512, 16)
    assert np.prod(shape) == 512 and "pod" in axes or len(shape) == 2


import numpy as np  # noqa: E402


def test_flash_decode_int8_cache_matches_fp():
    """Quantized-cache flash decoding ≈ fp cache attention (int8 tolerance)."""
    run_in_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.models.context import ParallelCtx
        from repro.models.flash_decode import flash_decode_attention
        from repro.models.layers import attention_dot, repeat_kv
        from repro.models.transformer import _cache_q

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                           flash_decode=True)
        b, smax, h, kv, hd = 4, 64, 8, 2, 16
        q = jax.random.normal(jax.random.PRNGKey(1), (b, 1, h, hd))
        ck = jax.random.normal(jax.random.PRNGKey(2), (b, smax, kv, hd))
        cv = jax.random.normal(jax.random.PRNGKey(3), (b, smax, kv, hd))
        kq, ks = _cache_q(ck)
        vq, vs = _cache_q(cv)
        pos = jnp.int32(41)
        with mesh:
            got = jax.jit(lambda *a: flash_decode_attention(*a[:3], a[3], pctx=pctx,
                                                            ks=a[4], vs=a[5]))(
                q, kq, vq, pos, ks, vs)
        want = attention_dot(q, repeat_kv(ck, h // kv), repeat_kv(cv, h // kv),
                             causal=True, q_offset=pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0.06, atol=0.05)
        print("int8 flash OK")
        """
    )
