"""Multi-device execution tests (subprocess: 8 host devices).

The main test process must keep the single real CPU device (conftest rule),
so shard_map behaviours — EP dispatch, distributed flash-decode, int8
compressed psum — execute in a child interpreter with
``--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_ep_matches_gspmd_reference():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import layers as ll
        from repro.distributed import hints
        from jax.sharding import AxisType
        from repro.distributed.moe_ep import moe_block_ep

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("deepseek-v3-671b").reduced()
        key = jax.random.PRNGKey(0)
        p = jax.tree.map(lambda a: a[0], ll.init_moe(cfg, key, 1, jnp.float32))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        cap = 1 << 20
        ref = ll.moe_block(cfg, p, x, cap)
        with hints.mesh_hints(mesh), mesh:
            out = jax.jit(lambda p, x: moe_block_ep(cfg, p, x, cap))(p, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        g1 = jax.grad(lambda p: (ll.moe_block(cfg, p, x, cap) ** 2).mean())(p)
        with hints.mesh_hints(mesh), mesh:
            g2 = jax.jit(jax.grad(
                lambda p: (moe_block_ep(cfg, p, x, cap) ** 2).mean()))(p)
        ge = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
        print("ERR", err, ge)
    """)
    err, gerr = [float(x) for x in out.split("ERR")[1].split()]
    assert err < 1e-4 and gerr < 1e-5


def test_flash_decode_matches_plain():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.distributed import hints
        from jax.sharding import AxisType
        from repro.distributed.flash_decode import (
            decode_attention_dist, seq_sharded_decode_applicable)
        from repro.models.layers import decode_attention

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        B, Smax, K, H, hd = 4, 32, 3, 6, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, 1, H, hd))
        kc = jax.random.normal(ks[1], (B, Smax, K, hd))
        vc = jax.random.normal(ks[2], (B, Smax, K, hd))
        kn = jax.random.normal(ks[3], (B, 1, K, hd))
        vn = jax.random.normal(ks[4], (B, 1, K, hd))
        assert seq_sharded_decode_applicable(mesh, B, Smax, K)
        errs = []
        for pos, w, cap in [(17, 0, 0.0), (9, 5, 30.0), (31, 0, 50.0)]:
            with hints.mesh_hints(mesh), mesh:
                od, kd, vd = jax.jit(lambda *a: decode_attention_dist(
                    *a, pos, window=w, softcap=cap))(q, kc, vc, kn, vn)
            kr = jax.lax.dynamic_update_slice_in_dim(kc, kn, pos, axis=1)
            vr = jax.lax.dynamic_update_slice_in_dim(vc, vn, pos, axis=1)
            orf = decode_attention(q, kr, vr, pos + 1, window=w, softcap=cap)
            errs.append(float(jnp.abs(od - orf).max()))
            errs.append(float(jnp.abs(kd - kr).max()))
        print("ERR", max(errs))
    """)
    assert float(out.split("ERR")[1]) < 1e-5


def test_train_step_on_8_device_mesh():
    """Full sharded train step (FSDP+TP) runs and loss decreases."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.models import transformer as T
        from repro.optim import adamw
        from repro.distributed.sharding import param_shardings, batch_spec
        from repro.distributed import hints
        from jax.sharding import AxisType

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("tinyllama-1.1b").reduced()
        with hints.mesh_hints(mesh), mesh:
            pshapes = jax.eval_shape(
                lambda k: T.init_params(cfg, k, dtype=jnp.float32),
                jax.random.PRNGKey(0))
            psh = param_shardings(pshapes, mesh)
            params = jax.jit(lambda k: T.init_params(cfg, k,
                                                     dtype=jnp.float32),
                             out_shardings=psh)(jax.random.PRNGKey(0))
            ocfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=0, total_steps=30)
            opt = adamw.init_state(params, ocfg)
            bsh = NamedSharding(mesh, batch_spec(mesh))

            @jax.jit
            def step(params, opt, batch):
                l, g = jax.value_and_grad(
                    lambda p: T.loss_fn(cfg, p, batch))(params)
                params, opt, _ = adamw.apply_updates(params, g, opt, ocfg)
                return params, opt, l

            losses = []
            for i in range(12):
                tok = jax.random.randint(jax.random.PRNGKey(i % 3),
                                         (8, 64), 0, cfg.vocab)
                tok = jax.device_put(tok, bsh)
                params, opt, l = step(params, opt, tok)
                losses.append(float(l))
        print("LOSS", losses[0], losses[-1])
    """)
    first, last = [float(x) for x in out.split("LOSS")[1].split()]
    assert last < first


def test_narrow_view_bucketed_correctness(scene_s, graph_s, hl_s, queries_s):
    """Width-bucketed routing returns exactly the full-width distances."""
    import jax.numpy as jnp
    from repro.core.grid import build_ehl
    from repro.core.compression import compress_to_fraction
    from repro.core.packed import (pack_index, pack_bucketed, query_batch,
                                   query_batch_bucketed)
    idx = build_ehl(scene_s, 2.0, graph=graph_s, hl=hl_s)
    compress_to_fraction(idx, 0.3)
    pk = pack_index(idx)
    bx = pack_bucketed(idx)
    s = jnp.asarray(queries_s.s.astype("float32"))
    t = jnp.asarray(queries_s.t.astype("float32"))
    full = query_batch(pk, s, t)
    buck = query_batch_bucketed(bx, s, t)
    np.testing.assert_allclose(buck, np.asarray(full), rtol=0, atol=0)
