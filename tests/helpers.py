"""Shared test fixtures: a small OP-DAG MLP chain (stand-in for a model),
and the repo-root ``chip_smoke.py`` loaded as a module."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.opgraph import OpGraph, OpNode, OpType


def linear_node(name, arg, din, dout):
    def init(rng, in_shape):
        return {"w": jax.random.normal(rng, (din, dout)) * (din ** -0.5),
                "b": jnp.zeros(dout)}

    def apply(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    return OpNode(name=name, op_type=OpType.PARAMETRIC, args=(arg,),
                  init_fn=init, apply_fn=apply,
                  out_shape_fn=lambda s: (s[0], dout),
                  flops_fn=lambda s: 2.0 * s[0] * din * dout,
                  n_params_fn=lambda s: din * dout + dout)


def mlp_chain(n_layers=6, d=16, batch=4, seed=0):
    g = OpGraph("mlp")
    g.add(OpNode("x", OpType.PLACEHOLDER))
    prev = "x"
    for i in range(n_layers):
        g.add(linear_node(f"l{i}", prev, d, d))
        prev = f"l{i}"
    g.add(OpNode("y", OpType.PLACEHOLDER))
    g.add(OpNode("loss", OpType.LOSS, args=(prev, "y"),
                 apply_fn=lambda p, a, b: jnp.mean((a - b) ** 2),
                 out_shape_fn=lambda *s: (),
                 flops_fn=lambda *s: float(np.prod(s[0]))))
    shapes = {"x": (batch, d), "y": (batch, d)}
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = g.init(k1, shapes)
    inputs = {"x": jax.random.normal(k2, (batch, d)),
              "y": jax.random.normal(k3, (batch, d))}
    return g, shapes, params, inputs


def load_chip_smoke():
    """``chip_smoke.py`` (a script at the repo root, not a package module)."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
