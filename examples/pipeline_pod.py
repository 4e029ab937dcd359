"""FusionLLM-on-a-pod: the shard_map GPipe pipeline with AdaTopK-compressed
pod-boundary edges, on whatever devices JAX sees (2 'pods' x n/2 stages;
one stage per device).

Verifies that the pipeline loss matches the single-device loss when
compression is off, then shows the compressed variant running.

    PYTHONPATH=src python examples/pipeline_pod.py
    # 8 simulated stages on a CPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/pipeline_pod.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import resolve
from repro.distributed.pipeline import (make_pipeline_train_fn, microbatch,
                                        n_stages, place_params,
                                        pod_edge_ratios)
from repro.models import causal_lm

devices = jax.devices()
pods = 2 if len(devices) % 2 == 0 else 1
mesh = Mesh(np.array(devices).reshape(pods, -1), ("pod", "model"))
cfg = resolve("gpt2-xl").smoke.replace(n_layers=2 * n_stages(mesh),
                                       max_seq=64)
print(f"stages: {n_stages(mesh)} (pod-crossing edge gets compressed)")
print("edge ratios (Eq. 7):", pod_edge_ratios(mesh, base_ratio=10.0))

B, S, n_micro = 8, 64, 4
rng = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
         "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab)}
mb = microbatch(batch, n_micro)

with jax.set_mesh(mesh):
    params = place_params(cfg, mesh, jax.random.PRNGKey(0))
    loss_fn = jax.jit(make_pipeline_train_fn(cfg, mesh, n_micro,
                                             base_ratio=1.0))
    loss = loss_fn(params, mb)
    loss_c_fn = jax.jit(make_pipeline_train_fn(cfg, mesh, n_micro,
                                               base_ratio=10.0))
    loss_c = loss_c_fn(params, mb)
    # gradients flow through the compressed pipeline (RAD through shard_map)
    g = jax.grad(lambda p: loss_c_fn(p, mb))(params)

# reference: single-device loss
ref_loss, _ = causal_lm.train_loss(
    cfg, jax.device_put(params, devices[0]), jax.device_put(batch, devices[0]))
print(f"pipeline loss {float(loss):.4f}  vs single-device "
      f"{float(ref_loss):.4f}")
assert abs(float(loss) - float(ref_loss)) < 1e-2
print(f"with AdaTopK on the pod boundary: loss {float(loss_c):.4f}")
gn = float(jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree_util.tree_leaves(g))))
print(f"grad norm through compressed pipeline: {gn:.4f}")
