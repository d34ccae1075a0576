"""GPipe pipeline over the pod axis == plain forward (exactness + grads)."""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_pipeline_forward_and_grad_match():
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import transformer as T
        from repro.models import layers as ll
        from repro.distributed import hints
        from jax.sharding import AxisType
        from repro.distributed.pipeline import pipeline_forward

        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("tinyllama-1.1b").reduced()   # 4 layers, 2 stages
        params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab)
        _, hid = T.forward(cfg, params, toks, return_hidden=True)
        ref = ll.rmsnorm(hid, params["final_norm"], cfg.norm_eps)
        with hints.mesh_hints(mesh), mesh:
            out = jax.jit(lambda p, t: pipeline_forward(
                cfg, p, t, n_micro=4))(params, toks)
        e1 = float(jnp.max(jnp.abs(out - ref)))

        def loss_pp(p):
            h = pipeline_forward(cfg, p, toks, n_micro=4)
            return (h.astype(jnp.float32) ** 2).mean()

        def loss_ref(p):
            _, hd = T.forward(cfg, p, toks, return_hidden=True)
            h = ll.rmsnorm(hd, p["final_norm"], cfg.norm_eps)
            return (h.astype(jnp.float32) ** 2).mean()

        with hints.mesh_hints(mesh), mesh:
            g1 = jax.jit(jax.grad(loss_pp))(params)
        g2 = jax.grad(loss_ref)(params)
        e2 = max(float(jnp.max(jnp.abs(a - b)))
                 for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
        print("ERR", e1, e2)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    e1, e2 = [float(x) for x in out.stdout.split("ERR")[1].split()]
    assert e1 < 1e-5 and e2 < 1e-6
