"""The plain GPT-2 reference of ``gpt2.py``, with programs that compile once
per chip.

The steps, the model, the top-k edges, Adam and the readings are those of
:mod:`chipbench.reference.gpt2`, which this module imports and does not
change.  Only the way its per-leaf work is jitted differs:

* a layer's leaves go to the read, the update, the initialisation and the
  change read as a tuple in ``LAYER_LEAVES`` order, and the layer index as
  a traced number, so that every layer on a chip runs one compiled program
  (``gpt2.py`` keys them by ``h{i}.*`` and compiles each layer anew: at 48
  layers on four chips about 130 programs, a layer's read ~20 s of TPU
  compile);
* the sample of a leaf is gathered at its fixed positions, which are those
  of :func:`gpt2.sample` (a strided slice of the flattened leaf, which
  takes the TPU compiler ~20 s for a layer's leaves).

Both sides' readings are therefore the same numbers as with ``gpt2.py``.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from chipbench.reference import gpt2 as base
from chipbench.reference.gpt2 import (LAYER_LEAVES, layer_name, readings,
                                      seed_key)


def sample_positions(shape: Tuple[int, ...]) -> Tuple[np.ndarray, ...]:
    """The indices, per axis, of the elements :func:`gpt2.sample` takes
    from a leaf of ``shape``: every ``stride``-th element of the flattened
    leaf, at most ``SAMPLE`` of them."""
    n = int(np.prod(shape))
    stride = max(1, n // base.SAMPLE)
    count = min(base.SAMPLE, -(-n // stride))
    return np.unravel_index(np.arange(count) * stride, shape)


def sample(a: jax.Array) -> jax.Array:
    """:func:`gpt2.sample` of ``a``, gathered at its positions."""
    return a.astype(jnp.float32)[sample_positions(a.shape)]


def read(leaves: Sequence[jax.Array]) -> tuple:
    """(norm, sample) of each leaf."""
    return tuple((jnp.linalg.norm(a.astype(jnp.float32).ravel()), sample(a))
                 for a in leaves)


class Trainer(base.Trainer):
    """:class:`gpt2.Trainer`'s steps and readings, each unit of leaves (a
    layer, the embeddings, the head) handled by one program per chip."""

    def _jit(self):
        super()._jit()
        conf, opt = self.conf, self.opt

        def init(key, layer, names):
            if layer is None:
                return tuple(base.init_leaf(conf, key, n) for n in names)
            return tuple(base.init_layer_leaf(conf, key, layer, leaf)
                         for leaf in LAYER_LEAVES)

        self._init_unit = jax.jit(init, static_argnums=2)
        # zeros depend on no input's value, so each chip gets its own
        # program placing them there (else all would land on the first)
        self._zeros = {d: jax.jit(
            lambda t: tuple(jnp.zeros_like(a) for a in t),
            out_shardings=SingleDeviceSharding(d)) for d in self.devices}
        self._read_unit = jax.jit(read)
        self._adam_unit = jax.jit(
            lambda p, m, v, g, t: tuple(base.adam(opt, *a, t)
                                        for a in zip(p, m, v, g)),
            donate_argnums=(0, 1, 2))
        self._delta_unit = jax.jit(
            lambda p, key, layer, names: read(tuple(
                a - b for a, b in zip(p, init(key, layer, names)))),
            static_argnums=3)

    def _placed_units(self):
        """(names, device, layer index or None) of each unit."""
        out = [(("wte", "wpe"), self.devices[0], None),
               (("lnf.scale", "lnf.bias", "head"), self.layer_dev[-1], None)]
        return out + [(tuple(layer_name(i, leaf) for leaf in LAYER_LEAVES),
                       dev, i) for i, dev in enumerate(self.layer_dev)]

    def _unit_args(self, key: Dict, dev, layer, names):
        """The key on ``dev``, the layer index and the static names (a
        layer's program is named by ``LAYER_LEAVES`` alone)."""
        if layer is None:
            return key[dev], None, names
        return key[dev], jnp.int32(layer), LAYER_LEAVES

    def _run(self, seed, batches, steps):
        one = {d: SingleDeviceSharding(d) for d in self.devices}
        key = {d: jax.device_put(seed_key(seed), one[d])
               for d in self.devices}
        units = self._placed_units()
        params, m, v = {}, {}, {}
        for names, dev, layer in units:
            made = self._init_unit(*self._unit_args(key, dev, layer, names))
            params.update(zip(names, made))
            m.update(zip(names, self._zeros[dev](made)))
            v.update(zip(names, self._zeros[dev](made)))
        losses, grad1 = [], {}
        for t in range(1, steps + 1):
            loss, grads = self._loss_and_grads(params, batches[t - 1])
            losses.append(float(loss))
            for names, _, _ in units:
                g = tuple(grads.pop(n) for n in names)
                if t == 1:
                    grad1.update(zip(names, self._read_unit(g)))
                new = self._adam_unit(tuple(params.pop(n) for n in names),
                                      tuple(m.pop(n) for n in names),
                                      tuple(v.pop(n) for n in names), g,
                                      jnp.float32(t))
                del g
                for n, (p_, m_, v_) in zip(names, new):
                    params[n], m[n], v[n] = p_, m_, v_
        del m, v
        delta = {}
        for names, dev, layer in units:
            k, i, static = self._unit_args(key, dev, layer, names)
            delta.update(zip(names, self._delta_unit(
                tuple(params.pop(n) for n in names), k, i, static)))
        return readings(losses, grad1, delta)

