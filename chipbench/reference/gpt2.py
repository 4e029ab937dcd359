"""Plain GPT-2 training reference: loss, gradients and Adam, layer by layer.

Straightforward ``jax.numpy`` that imports nothing of the program.  It makes
its own weights from the seed (``init_params``, which the benchmark also
uses to hand the program its weights), and follows the program's first
training steps on the same batches:

* pre-LayerNorm blocks (eps from the configuration), causal multi-head
  attention with scores in float32 and masked to -1e30, GELU in its tanh
  form, no biases on the projections, learned positions, an untied head;
* the padded vocabulary rows either take part in the softmax
  (``pad_logits: in_softmax``) or are masked out (``masked``), as the
  configuration states for the path;
* top-k on the edges the run compresses: per block of ``block`` elements of
  the flattened tensor, the ``k_per_block`` largest magnitudes are kept
  (ties to the lower index), forward on the activation and backward on its
  gradient;
* Adam with bias correction (``lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)``).

The model is run one layer at a time, each layer on the device that holds
it, so that a 48-layer model with its Adam state fits across four chips and
a 12-layer one on one chip.  ``variant`` selects what is computed:
``"reference"`` (float32 at the matmul precision the configuration
states, unless the caller names another), ``"bf16"`` (the control: the same steps in bfloat16), and the
faults ``"half_batch"`` (the loss over the first half of the rows) and
``"no_exchange"`` (nothing crosses between devices: the receiving layer reads
zeros and sends back no gradient).

Besides the norms, the readings keep a fixed sample of every leaf's first
gradient and of its change over the steps (:func:`sample`), so that the two
sides can be compared element by element.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

GLOBAL_LEAVES = ("wte", "wpe", "lnf.scale", "lnf.bias", "head")
LAYER_LEAVES = ("ln1.scale", "ln1.bias", "wq", "wk", "wv", "wo",
                "ln2.scale", "ln2.bias", "up", "down")
VARIANTS = ("reference", "bf16", "half_batch", "no_exchange")
#: elements of a leaf that the element-wise comparison reads
SAMPLE = 65536


def sample(a: jax.Array) -> jax.Array:
    """A fixed sample of ``a``: every element of a leaf of up to ``SAMPLE``
    elements, else ``SAMPLE`` elements evenly strided over the flattened
    leaf (traceable; both sides take the same positions)."""
    flat = a.reshape(-1).astype(jnp.float32)
    stride = max(1, flat.shape[0] // SAMPLE)
    return flat[::stride][:SAMPLE]


def seed_key(seed: int) -> jax.Array:
    """PRNG key of a seed of any size (the low and high 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def layer_name(layer: int, leaf: str) -> str:
    return f"h{layer}.{leaf}"


def leaf_names(conf: dict, layers: Optional[Sequence[int]] = None,
               globals_: Sequence[str] = GLOBAL_LEAVES) -> List[str]:
    layers = range(conf["n_layer"]) if layers is None else layers
    return list(globals_) + [layer_name(i, leaf) for i in layers
                             for leaf in LAYER_LEAVES]


def _d_ff(conf: dict) -> int:
    return conf.get("n_inner") or 4 * conf["n_embd"]


def padded_vocab(conf: dict) -> int:
    pad = conf.get("vocab_pad_to", 1)
    return -(-conf["vocab_size"] // pad) * pad


def leaf_shape(conf: dict, name: str):
    d, f = conf["n_embd"], _d_ff(conf)
    leaf = name if name in GLOBAL_LEAVES else name.split(".", 1)[1]
    return {"wte": (padded_vocab(conf), d), "wpe": (conf["n_positions"], d),
            "head": (d, padded_vocab(conf)), "wq": (d, d), "wk": (d, d),
            "wv": (d, d), "wo": (d, d), "up": (d, f), "down": (f, d),
            }.get(leaf, (d,))


def _draw(conf: dict, key: jax.Array, leaf: str, leaf_id, shape
          ) -> jax.Array:
    if leaf.endswith(".scale"):
        return jnp.ones(shape, jnp.float32)
    if leaf.endswith(".bias"):
        return jnp.zeros(shape, jnp.float32)
    std = conf.get("initializer_range", 0.02)
    if leaf == "wpe":
        std = 0.01
    elif leaf in ("wo", "down"):
        std = std / math.sqrt(2 * conf["n_layer"])
    return std * jax.random.normal(jax.random.fold_in(key, leaf_id), shape,
                                   jnp.float32)


def init_leaf(conf: dict, key: jax.Array, name: str) -> jax.Array:
    """GPT-2's initialisation: N(0, 0.02) weights, N(0, 0.01) positions,
    residual projections scaled by 1/sqrt(2 n_layer), LayerNorm at (1, 0).
    Each leaf draws from its own key, so any subset is made alike."""
    shape = leaf_shape(conf, name)
    if name in GLOBAL_LEAVES:
        return _draw(conf, key, name, GLOBAL_LEAVES.index(name), shape)
    layer, leaf = name[1:].split(".", 1)
    return init_layer_leaf(conf, key, int(layer), leaf)


def init_layer_leaf(conf: dict, key: jax.Array, layer, leaf: str
                    ) -> jax.Array:
    """Leaf ``leaf`` of layer ``layer`` (which may be traced, so that
    ``vmap`` over layers makes a stack equal to the leaves one by one)."""
    shape = leaf_shape(conf, layer_name(0, leaf))
    return _draw(conf, key, leaf, 16 + 16 * layer + LAYER_LEAVES.index(leaf),
                 shape)


def init_params(conf: dict, key: jax.Array, names: Sequence[str]
                ) -> Dict[str, jax.Array]:
    return {n: init_leaf(conf, key, n) for n in names}


# ------------------------------------------------------------------ model --

def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def block(conf: dict, p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """One pre-LayerNorm transformer block on x (B, S, d)."""
    cd, eps = x.dtype, conf["layer_norm_epsilon"]
    b, s, d = x.shape
    heads = conf["n_head"]
    hd = d // heads

    def w(n):
        return p[n].astype(cd)

    h = layer_norm(x, p["ln1.scale"], p["ln1.bias"], eps)
    q = (h @ w("wq")).reshape(b, s, heads, hd)
    k = (h @ w("wk")).reshape(b, s, heads, hd)
    v = (h @ w("wv")).reshape(b, s, heads, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cd), v)
    x = x + o.reshape(b, s, d) @ w("wo")
    h = layer_norm(x, p["ln2.scale"], p["ln2.bias"], eps)
    return x + gelu(h @ w("up")) @ w("down")


def topk_blocks(x: jax.Array, k_per_block: int, block_size: int) -> jax.Array:
    """Keep the ``k_per_block`` largest magnitudes of every block of the
    flattened tensor (ties to the lower index), zero the rest."""
    flat = x.reshape(-1)
    if flat.shape[0] % block_size:
        raise ValueError(f"{flat.shape[0]} elements do not fill blocks of "
                         f"{block_size}")
    tiles = flat.reshape(-1, block_size)
    _, idx = jax.lax.top_k(jnp.abs(tiles).astype(jnp.float32), k_per_block)
    rows = jnp.arange(tiles.shape[0])[:, None]
    keep = jnp.zeros(tiles.shape, bool).at[rows, idx].set(True)
    return jnp.where(keep, tiles, 0).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sparsify(x, k_per_block, block_size):
    return topk_blocks(x, k_per_block, block_size)


def _sparsify_fwd(x, k_per_block, block_size):
    return topk_blocks(x, k_per_block, block_size), None


def _sparsify_bwd(k_per_block, block_size, _, g):
    return (topk_blocks(g, k_per_block, block_size),)


sparsify.defvjp(_sparsify_fwd, _sparsify_bwd)


def embed(conf, p, tokens, cd):
    s = tokens.shape[1]
    return (jnp.take(p["wte"], tokens, axis=0)
            + p["wpe"][:s][None]).astype(cd)


def head_loss(conf, p, x, labels, logits_k, block_size, rows):
    """Mean next-token cross-entropy over the first ``rows`` rows."""
    h = layer_norm(x, p["lnf.scale"], p["lnf.bias"],
                   conf["layer_norm_epsilon"])
    logits = h @ p["head"].astype(x.dtype)
    if conf["pad_logits"] == "masked":
        valid = jnp.arange(logits.shape[-1]) < conf["vocab_size"]
        logits = jnp.where(valid, logits, -1e30)
    if logits_k:
        logits = sparsify(logits, logits_k, block_size)
    logits = logits[:rows].astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:rows][..., None], axis=-1)
    return jnp.mean(lse - gold[..., 0])


def adam(opt, p, m, v, g, t):
    b1, b2, eps, lr, wd = (opt["b1"], opt["b2"], opt["eps"], opt["lr"],
                           opt["weight_decay"])
    g = g.astype(jnp.float32)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return p - lr * (upd + wd * p), m, v


def read_leaves(tree):
    """{leaf: (norm, sample)}."""
    return {n: (jnp.linalg.norm(a.astype(jnp.float32).ravel()), sample(a))
            for n, a in tree.items()}


# -------------------------------------------------------------- the steps --

def readings(losses, grad1, delta) -> "Readings":
    """Readings from the losses and {leaf: (norm, sample)} of the first
    gradient and of the change, fetched to the host."""
    def split(d):
        return ({n: float(x) for n, (x, _) in d.items()},
                {n: np.asarray(y) for n, (_, y) in d.items()})
    g, gs = split(grad1)
    d, ds = split(delta)
    return Readings([float(x) for x in losses], g, d, gs, ds)


@dataclasses.dataclass
class Readings:
    """What is compared: each step's loss, every leaf's first gradient norm,
    and every leaf's norm of change over the steps, with the samples
    (:func:`sample`) of the same first gradient and change."""
    losses: List[float]
    grad1: Dict[str, float]
    delta: Dict[str, float]
    grad1_sample: Dict[str, np.ndarray]
    delta_sample: Dict[str, np.ndarray]


class Trainer:
    """The reference's training steps on ``devices``: layer ``i`` on
    ``devices[i * len(devices) // n_layer]``, embeddings on the first
    device, final norm and head on the last."""

    def __init__(self, conf: dict, edges: Sequence[dict], block_size: int,
                 opt: dict, devices: Sequence, variant: str = "reference",
                 precision: Optional[str] = None):
        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}")
        self.conf, self.opt, self.variant = conf, opt, variant
        self.block_size = block_size
        self.devices = list(devices)
        n = conf["n_layer"]
        self.layer_dev = [self.devices[i * len(self.devices) // n]
                          for i in range(n)]
        self.layer_k = {}
        self.logits_k = 0
        for e in edges:
            if e["after"] == "head":
                self.logits_k = e["k_per_block"]
            elif e["after"] == "embed":
                self.layer_k[-1] = e["k_per_block"]
            else:
                self.layer_k[int(e["after"].rsplit("_", 1)[1])] = \
                    e["k_per_block"]
        self.cd = jnp.bfloat16 if variant == "bf16" else jnp.float32
        self.precision = precision or conf["matmul_precision"]
        self._jit()

    # one compiled program per function, reused on every device
    def _jit(self):
        conf, cd, bs = self.conf, self.cd, self.block_size

        def blk(p, x):
            return block(conf, p, x)

        self._block = jax.jit(blk)
        self._block_vjp = jax.jit(lambda p, x, g: jax.vjp(blk, p, x)[1](g))
        self._sparsify = jax.jit(topk_blocks, static_argnums=(1, 2))
        self._embed = jax.jit(lambda p, t: embed(conf, p, t, cd))
        self._embed_vjp = jax.jit(lambda p, t, g: jax.vjp(
            lambda q: embed(conf, q, t, cd), p)[1](g)[0])
        self._head = jax.jit(
            lambda p, x, y, rows: jax.value_and_grad(
                lambda q, z: head_loss(conf, q, z, y, self.logits_k, bs,
                                       rows), argnums=(0, 1))(p, x),
            static_argnums=(3,))
        self._adam = jax.jit(lambda p, m, v, g, t: jax.tree_util.tree_map(
            lambda *a: adam(self.opt, *a, t), p, m, v, g),
            donate_argnums=(0, 1, 2))
        self._read = jax.jit(read_leaves)
        self._delta = jax.jit(
            lambda p, key: read_leaves({n: p[n] - init_leaf(conf, key, n)
                                        for n in p}))

    def _groups(self):
        """{device: names of the leaves it holds}."""
        out = {}
        for i, dev in enumerate(self.layer_dev):
            out.setdefault(dev, []).extend(
                layer_name(i, leaf) for leaf in LAYER_LEAVES)
        out.setdefault(self.devices[0], []).extend(("wte", "wpe"))
        out.setdefault(self.layer_dev[-1], []).extend(
            ("lnf.scale", "lnf.bias", "head"))
        return out

    def _units(self):
        """Leaves updated together: each layer's, the embeddings', the
        head's (small, so that the update's new buffers stay small)."""
        return ([["wte", "wpe"], ["lnf.scale", "lnf.bias", "head"]]
                + [[layer_name(i, leaf) for leaf in LAYER_LEAVES]
                   for i in range(self.conf["n_layer"])])

    def run(self, seed: int, batches: Sequence[dict], steps: int = 3
            ) -> Readings:
        """``steps`` Adam steps from the seed's weights on ``batches``."""
        with jax.default_matmul_precision(self.precision):
            return self._run(seed, batches, steps)

    def _run(self, seed, batches, steps):
        conf, key = self.conf, seed_key(seed)
        groups = self._groups()
        params, m, v = {}, {}, {}
        for dev, names in groups.items():
            one = SingleDeviceSharding(dev)
            made = jax.jit(functools.partial(init_params, conf, names=names),
                           out_shardings=one)(jax.device_put(key, one))
            params.update(made)
            zeros = jax.jit(lambda t: jax.tree_util.tree_map(
                jnp.zeros_like, t), out_shardings=one)
            m.update(zeros(made))
            v.update(zeros(made))
        losses, grad1 = [], {}
        for t in range(1, steps + 1):
            loss, grads = self._loss_and_grads(params, batches[t - 1])
            losses.append(float(loss))
            for names in self._units():
                g = {n: grads.pop(n) for n in names}
                if t == 1:
                    grad1.update(self._read(g))
                new = self._adam({n: params.pop(n) for n in names},
                                 {n: m.pop(n) for n in names},
                                 {n: v.pop(n) for n in names}, g,
                                 jnp.float32(t))
                del g
                for n in names:
                    params[n], m[n], v[n] = new[n]
        del m, v
        delta = {}
        for dev, names in groups.items():
            one = SingleDeviceSharding(dev)
            delta.update(self._delta({n: params.pop(n) for n in names},
                                     jax.device_put(key, one)))
        return readings(losses, grad1, delta)

    def _loss_and_grads(self, params, batch):
        conf, n = self.conf, self.conf["n_layer"]
        first, last = self.devices[0], self.layer_dev[-1]
        tokens = jax.device_put(batch["tokens"], first)
        labels = jax.device_put(batch["labels"], last)
        rows = tokens.shape[0] // 2 if self.variant == "half_batch" \
            else tokens.shape[0]
        pe = {k: params[k] for k in ("wte", "wpe")}
        x = self._embed(pe, tokens)
        if -1 in self.layer_k:
            x = self._sparsify(x, self.layer_k[-1], self.block_size)
        inputs = []
        for i in range(n):
            x = self._cross(x, i)
            inputs.append(x)
            x = self._block(self._layer(params, i), x)
            if i in self.layer_k:
                x = self._sparsify(x, self.layer_k[i], self.block_size)
        ph = {k: params[k] for k in ("lnf.scale", "lnf.bias", "head")}
        loss, (gh, g) = self._head(ph, jax.device_put(x, last), labels, rows)
        grads = dict(gh)
        for i in range(n - 1, -1, -1):
            if i in self.layer_k:
                g = self._sparsify(g, self.layer_k[i], self.block_size)
            g = jax.device_put(g, self.layer_dev[i])
            gp, g = self._block_vjp(self._layer(params, i), inputs[i], g)
            grads.update({layer_name(i, k): a for k, a in gp.items()})
            if (self.variant == "no_exchange" and i > 0
                    and self.layer_dev[i - 1] != self.layer_dev[i]):
                g = jnp.zeros_like(g)
        del inputs
        if -1 in self.layer_k:
            g = self._sparsify(g, self.layer_k[-1], self.block_size)
        grads.update(self._embed_vjp(pe, tokens,
                                     jax.device_put(g, first)))
        return loss, grads

    def _cross(self, x, i):
        """The activation into layer ``i``, moved to its device."""
        dev = self.layer_dev[i]
        if (self.variant == "no_exchange" and i > 0
                and self.layer_dev[i - 1] != dev):
            return jax.device_put(jnp.zeros_like(x), dev)
        return jax.device_put(x, dev)

    @staticmethod
    def _layer(params, i):
        return {k: params[layer_name(i, k)] for k in LAYER_LEAVES}
