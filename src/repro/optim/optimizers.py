"""Optimizers as pure pytree transforms (no optax dependency).

Each optimizer is an (init, update) pair packaged in :class:`Optimizer`,
its update traced inside the ``optim`` device scope (:mod:`repro.obs.scopes`);
state and params are arbitrary pytrees, so the same code drives the GSPMD
train step (sharded state), the FusionLLM decentralized runtime (per-
CompNode sub-trees — the paper's per-OP "Update" stage, §3.3), and unit
tests.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import OPTIM


class OptState(NamedTuple):
    step: jax.Array
    inner: Any


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], OptState]
    update: Callable[[Any, OptState, Any], Tuple[Any, OptState]]
    # update(grads, state, params) -> (new_params, new_state)


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _scoped(update):
    """``update`` inside the optimizer's device scope."""
    @functools.wraps(update)
    def scoped(grads, state, params):
        with jax.named_scope(OPTIM):
            return update(grads, state, params)
    return scoped


# -------------------------------------------------------------- schedules --
def cosine_schedule(base_lr: float, total_steps: int, final_frac: float = 0.1
                    ) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        t = jnp.minimum(step.astype(jnp.float32), total_steps) / total_steps
        return base_lr * (final_frac + (1 - final_frac)
                          * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return lr


def linear_warmup_cosine(base_lr: float, warmup: int, total_steps: int,
                         final_frac: float = 0.1
                         ) -> Callable[[jax.Array], jax.Array]:
    cos = cosine_schedule(base_lr, max(total_steps - warmup, 1), final_frac)

    def lr(step):
        s = step.astype(jnp.float32)
        return jnp.where(s < warmup, base_lr * (s + 1) / warmup,
                         cos(jnp.maximum(s - warmup, 0)))
    return lr


def _as_sched(lr) -> Callable[[jax.Array], jax.Array]:
    if callable(lr):
        return lr
    return lambda step: jnp.asarray(lr, jnp.float32)


# ------------------------------------------------------------------- clip --
def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree_util.tree_leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return _tmap(lambda g: (g * scale).astype(g.dtype), grads), gn


# -------------------------------------------------------------------- SGD --
def sgd(lr=1e-2, momentum: float = 0.9, nesterov: bool = False,
        weight_decay: float = 0.0) -> Optimizer:
    sched = _as_sched(lr)

    def init(params):
        mom = _tmap(jnp.zeros_like, params) if momentum else None
        return OptState(step=jnp.zeros((), jnp.int32), inner=mom)

    def update(grads, state, params):
        lr_t = sched(state.step)
        if weight_decay:
            grads = _tmap(lambda g, p: g + weight_decay * p, grads, params)
        if momentum:
            mom = _tmap(lambda m, g: momentum * m + g, state.inner, grads)
            eff = _tmap(lambda m, g: momentum * m + g, mom, grads) \
                if nesterov else mom
            inner = mom
        else:
            eff, inner = grads, None
        new_p = _tmap(lambda p, g: (p - lr_t * g).astype(p.dtype), params, eff)
        return new_p, OptState(step=state.step + 1, inner=inner)

    return Optimizer(init=init, update=_scoped(update))


# ------------------------------------------------------------------ AdamW --
def adamw(lr=3e-4, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.1) -> Optimizer:
    sched = _as_sched(lr)

    def init(params):
        return OptState(
            step=jnp.zeros((), jnp.int32),
            inner={"m": _tmap(lambda p: jnp.zeros_like(p, jnp.float32), params),
                   "v": _tmap(lambda p: jnp.zeros_like(p, jnp.float32), params)})

    def update(grads, state, params):
        t = state.step + 1
        lr_t = sched(state.step)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        m = _tmap(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                  state.inner["m"], grads)
        v = _tmap(lambda v_, g: b2 * v_ + (1 - b2)
                  * jnp.square(g.astype(jnp.float32)),
                  state.inner["v"], grads)

        def step_fn(p, m_, v_):
            upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            return (p - lr_t * (upd + weight_decay * p.astype(jnp.float32))
                    ).astype(p.dtype)
        new_p = _tmap(step_fn, params, m, v)
        return new_p, OptState(step=t, inner={"m": m, "v": v})

    return Optimizer(init=init, update=_scoped(update))


# -------------------------------------------------------------- Adafactor --
def adafactor(lr=1e-3, decay: float = 0.8, eps: float = 1e-30,
              clip_threshold: float = 1.0) -> Optimizer:
    """Factored second moment for matrices (memory-lean option for the
    biggest configs); falls back to full accumulators on <2D leaves."""
    sched = _as_sched(lr)

    def _facts(p):
        if p.ndim < 2:
            return {"v": jnp.zeros_like(p, jnp.float32)}
        return {"r": jnp.zeros(p.shape[:-1], jnp.float32),
                "c": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}

    def init(params):
        return OptState(step=jnp.zeros((), jnp.int32),
                        inner=_tmap(_facts, params))

    def update(grads, state, params):
        t = state.step + 1
        lr_t = sched(state.step)
        beta = 1.0 - (t.astype(jnp.float32) + 1.0) ** (-decay)

        def upd(p, g, f):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + eps
            if p.ndim < 2:
                v = beta * f["v"] + (1 - beta) * g2
                u = g32 / jnp.sqrt(v + eps)
                nf = {"v": v}
            else:
                r = beta * f["r"] + (1 - beta) * jnp.mean(g2, axis=-1)
                c = beta * f["c"] + (1 - beta) * jnp.mean(g2, axis=-2)
                denom = (r[..., None] * c[..., None, :]
                         / jnp.maximum(jnp.mean(r, axis=-1, keepdims=True)
                                       [..., None], eps))
                u = g32 / jnp.sqrt(denom + eps)
                nf = {"r": r, "c": c}
            rms = jnp.sqrt(jnp.mean(jnp.square(u)) + eps)
            u = u / jnp.maximum(1.0, rms / clip_threshold)
            return (p - lr_t * u).astype(p.dtype), nf

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_f = tdef.flatten_up_to(state.inner)
        outs = [upd(p, g, f) for p, g, f in zip(flat_p, flat_g, flat_f)]
        new_p = tdef.unflatten([o[0] for o in outs])
        new_f = tdef.unflatten([o[1] for o in outs])
        return new_p, OptState(step=t, inner=new_f)

    return Optimizer(init=init, update=_scoped(update))
