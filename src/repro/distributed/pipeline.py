"""FusionLLM pipeline on a TPU mesh (DESIGN.md §4 path 2).

The paper's runtime — inter-layer stages, boundary activations/gradients on
links, Top-K compression on the *slowest* links — mapped onto jax-native
constructs:

* stage axis = the mesh's ``model`` axis (single pod) or the flattened
  ``pod × model`` axes (multi-pod): consecutive stages sit on neighbouring
  chips, and exactly the stage boundaries that cross the pod boundary ride
  the slow links;
* boundary transfer = ``jax.lax.ppermute`` inside ``shard_map``;
* AdaTopK = :func:`repro.core.compression.boundary_compress` applied to the
  boundary tensor *before* the permute, with a per-edge ratio from Eq. 7 —
  pod-crossing edges get ``3r``, intra-pod edges ratio 1 (no compression),
  exactly the adaptive schedule the paper derives for heterogeneous links;
* schedule = GPipe (paper Eq. 3): ``n_micro + n_stages - 1`` ticks, stage s
  processes micro-batch ``t - s`` at tick t;
* RAD = ``jax.grad`` *through* the shard_map — each stage's backward runs
  where its forward ran and boundary gradients flow over the reversed
  permute, compressed by the same per-edge plan (``boundary_compress`` is a
  custom_vjp whose backward sparsifies the cotangent).

Each part of a tick runs in a ``jax.named_scope`` of
:mod:`repro.obs.scopes` (``pipe/embed``, ``pipe/blocks``, ``pipe/head``,
``pipe/edge/{after}``, ``pipe/exchange``), and the tick loop in
``pipe/ticks``, so a device trace splits the step by part; scopes change
metadata only.

Supports the dense/GPT-2 family (homogeneous blocks — the paper's own
workload).  n_layers must divide evenly into stages.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelCfg
from repro.core.compression import (KernelPolicy, boundary_compress,
                                    ratio_to_k)
from repro.models import causal_lm
from repro.models.causal_lm import _dense_block
from repro.models.layers import cross_entropy, embed, norm_apply
from repro.obs.scopes import (BLOCKS, EMBED, EXCHANGE, HEAD, TICKS,
                              pipe_edge_scope, pipe_scope)


def stage_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "model") if "pod" in mesh.axis_names else ("model",)


def n_stages(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in stage_axes(mesh)]))


def pod_edge_ratios(mesh: Mesh, base_ratio: float,
                    index_overhead: float = 3.0) -> np.ndarray:
    """Per-boundary compression ratio (edge s -> s+1), Eq. 7.

    R_i for an intra-pod ICI edge vs a pod-crossing edge differs by ~the
    bandwidth gap; with only two tiers, Eq. 7 degenerates to: slowest edges
    get ``3r``, fast edges get 1 (max(1, 3r·R_i/R_max) with R_i ≪ R_max).
    A slow ratio at or below the encoding's break-even (``index_overhead``,
    i.e. ``base_ratio <= 1``) would inflate the wire, so it sends dense —
    the same clamp as :func:`repro.core.compression.adaptive_ratios`.
    """
    ns = n_stages(mesh)
    ratios = np.ones(ns)            # edge i: stage i -> i+1 (cyclic unused)
    slow = index_overhead * base_ratio
    if "pod" in mesh.axis_names and slow > index_overhead:
        per_pod = mesh.shape["model"]
        for s in range(ns - 1):
            if (s + 1) % per_pod == 0:           # crossing into next pod
                ratios[s] = slow
    return ratios


def _split_stage_params(cfg: ModelCfg, params: Dict[str, Any], ns: int):
    """Reshape stacked block params (L, ...) -> (ns, L/ns, ...); embed/head
    replicated (stage 0 / last stage use them)."""
    L = cfg.n_layers
    if L % ns:
        raise ValueError(f"{L} layers not divisible into {ns} stages")
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((ns, L // ns) + a.shape[1:]), params["blocks"])
    rest = {k: v for k, v in params.items() if k != "blocks"}
    return blocks, rest


def place_params(cfg: ModelCfg, mesh: Mesh, rng: jax.Array
                 ) -> Dict[str, Any]:
    """``causal_lm.init`` materialized directly in the stage placement, so
    no device ever holds the whole model: the stacked blocks split along
    their layer axis over the stage axes (each chip holds exactly its
    stage's layers, in the order :func:`_split_stage_params` cuts them);
    embeddings, final norm and head replicated."""
    def sharding(key):
        return NamedSharding(mesh, P(stage_axes(mesh)) if key == "blocks"
                             else P())

    init = functools.partial(causal_lm.init, cfg)
    shardings = {k: jax.tree_util.tree_map(lambda _: sharding(k), v)
                 for k, v in jax.eval_shape(init, rng).items()}
    return jax.jit(init, out_shardings=shardings)(rng)


def make_pipeline_train_fn(cfg: ModelCfg, mesh: Mesh, n_micro: int,
                           base_ratio: float = 1.0,
                           use_kernel: KernelPolicy = False) -> Callable:
    """Returns loss_fn(params, batch) running the GPipe schedule under
    shard_map.  batch tokens: (n_micro, mb, S).  Call it under
    ``jax.set_mesh(mesh)``: JAX 0.9 meshes have Explicit axes."""
    if cfg.family not in ("dense",):
        raise NotImplementedError("pipeline path covers the dense family "
                                  "(the paper's GPT-2 workload)")
    axes = stage_axes(mesh)
    ns = n_stages(mesh)
    ratios = pod_edge_ratios(mesh, base_ratio)
    perm_fwd = [(i, i + 1) for i in range(ns - 1)]

    def loss_fn(params, batch):
        blocks, rest = _split_stage_params(cfg, params, ns)
        tokens, labels = batch["tokens"], batch["labels"]
        mb, S = tokens.shape[1], tokens.shape[2]
        d = cfg.d_model

        blk_specs = jax.tree_util.tree_map(lambda _: P(axes), blocks)
        rest_specs = jax.tree_util.tree_map(lambda _: P(), rest)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(blk_specs, rest_specs, P(), P()),
            out_specs=P(), check_vma=True)
        def run(blocks_l, rest_l, tok, lab):
            # blocks_l leaves: (1, L/ns, ...) — this stage's layers
            my = jax.tree_util.tree_map(lambda a: a[0], blocks_l)
            stage = jax.lax.axis_index(axes[0])
            if len(axes) == 2:
                stage = stage * jax.lax.axis_size(axes[1]) \
                    + jax.lax.axis_index(axes[1])
            is_first = stage == 0
            is_last = stage == ns - 1

            @jax.named_scope(pipe_scope(EMBED))
            def embed_mb(i):
                x = embed(rest_l["embed"], tok[i], cfg.dtype)
                if cfg.rope_fraction == 0.0:
                    x = x + embed(rest_l["pos_embed"], jnp.arange(S),
                                  cfg.dtype)[None]
                return x

            @jax.named_scope(pipe_scope(BLOCKS))
            def run_blocks(x):
                # remat per block: the backward keeps each block's input for
                # every tick, not its attention scores and MLP activations
                @jax.checkpoint
                def body(h, pl):
                    return _dense_block(cfg, pl, h, cfg.window), None
                h, _ = jax.lax.scan(body, x, my)
                return h

            @jax.named_scope(pipe_scope(HEAD))
            def head_loss(x, i):
                h = norm_apply(cfg.norm, rest_l["final_norm"], x)
                logits = causal_lm._head(cfg, rest_l, h)
                return cross_entropy(logits.astype(jnp.float32), lab[i])

            # Eq. 7 per-edge compression of the OUTGOING boundary.  With two
            # bandwidth tiers every slow (pod-crossing) edge shares one
            # ratio 3r, so one static k suffices; which slow edge, if any,
            # leaves THIS stage is a traced index (lax.switch — one branch
            # runs), each edge's codec in its own scope.
            slow_edges = np.flatnonzero(ratios > 1.0)

            def compress_boundary(x):
                if not slow_edges.size:
                    return x
                k_comp = ratio_to_k(mb * S * d, float(ratios[slow_edges[0]]))

                def edge(s):
                    after = f"block_{(s + 1) * (cfg.n_layers // ns) - 1}"

                    @jax.named_scope(pipe_edge_scope(after))
                    def codec(v):
                        return boundary_compress(v, k_comp, k_comp,
                                                 use_kernel)
                    return codec

                branch = sum((stage == s) * (j + 1)
                             for j, s in enumerate(slow_edges))
                return jax.lax.switch(
                    branch, [lambda v: v] + [edge(s) for s in slow_edges], x)

            total_ticks = n_micro + ns - 1
            state0 = jnp.zeros((mb, S, d), cfg.dtype)   # incoming boundary

            def tick(carry, t):
                state, loss_acc = carry
                mb_idx = t - stage
                active = (mb_idx >= 0) & (mb_idx < n_micro)
                mb_safe = jnp.clip(mb_idx, 0, n_micro - 1)
                x_in = jnp.where(is_first, embed_mb(mb_safe), state)
                y = run_blocks(x_in)
                loss_mb = jnp.where(is_last & active,
                                    head_loss(y, mb_safe), 0.0)
                y = compress_boundary(y)
                with jax.named_scope(pipe_scope(EXCHANGE)):
                    nxt = jax.lax.ppermute(y, axes, perm_fwd)
                return (nxt, loss_acc + loss_mb), None

            # the carry differs per stage: type it as varying over the mesh
            carry0 = jax.lax.pcast((state0, jnp.zeros((), jnp.float32)),
                                   axes, to="varying")
            with jax.named_scope(pipe_scope(TICKS)):
                (state, loss_acc), _ = jax.lax.scan(tick, carry0,
                                                    jnp.arange(total_ticks))
            # only the last stage holds a non-zero loss
            return jax.lax.psum(loss_acc, axes)

        return run(blocks, rest, tokens, labels) / n_micro

    return loss_fn


def make_pipeline_train_step(cfg: ModelCfg, mesh: Mesh, optimizer,
                             n_micro: int, base_ratio: float = 1.0,
                             use_kernel: KernelPolicy = False):
    loss_fn = make_pipeline_train_fn(cfg, mesh, n_micro, base_ratio,
                                     use_kernel)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch))(params)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss}

    return step


def microbatch(batch: Dict[str, jax.Array], n_micro: int
               ) -> Dict[str, jax.Array]:
    out = {}
    for k, v in batch.items():
        B = v.shape[0]
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro {n_micro}")
        out[k] = v.reshape((n_micro, B // n_micro) + v.shape[1:])
    return out
