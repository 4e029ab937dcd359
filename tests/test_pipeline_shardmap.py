"""shard_map pipeline (paper runtime on a pod): correctness requires >1
device, so the check runs in a subprocess with forced host devices (the
main pytest process must keep seeing 1 device)."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.configs import resolve
    from repro.distributed.pipeline import (make_pipeline_train_fn,
                                            microbatch, pod_edge_ratios)
    from repro.models import causal_lm

    mesh = jax.make_mesh((2, 4), ("pod", "model"))
    with jax.set_mesh(mesh):
        cfg = resolve("gpt2-xl").smoke.replace(n_layers=8, max_seq=32)
        params = causal_lm.init(cfg, jax.random.PRNGKey(0))
        B, S, n_micro = 8, 32, 4
        rng = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
                 "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab)}
        mb = microbatch(batch, n_micro)
        ref, _ = causal_lm.train_loss(cfg, params, batch)
        loss = jax.jit(make_pipeline_train_fn(cfg, mesh, n_micro, 1.0))(params, mb)
        assert abs(float(loss) - float(ref)) < 2e-2, (float(loss), float(ref))
        # Eq.7 ratios: only the stage-3->4 edge (pod crossing) compresses
        r = pod_edge_ratios(mesh, 10.0)
        assert r[3] == 30.0 and all(x == 1.0 for i, x in enumerate(r) if i != 3)
        # grads flow through the compressed pipeline
        lc = make_pipeline_train_fn(cfg, mesh, n_micro, base_ratio=10.0)
        g = jax.grad(lambda p: lc(p, mb))(params)
        gn = sum(float(jnp.sum(jnp.abs(x))) for x in
                 jax.tree_util.tree_leaves(g))
        assert gn > 0 and jnp.isfinite(jnp.asarray(gn))
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_pipeline_matches_single_device_and_compresses():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=560,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert "PIPELINE_OK" in res.stdout, res.stdout + res.stderr
