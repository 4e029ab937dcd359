"""The benchmark's per-chip GPT-2 reference (``chipbench/reference/
gpt2_per_chip.py``) against the reference it rejits (``gpt2.py``): the
same sample positions at every leaf shape of the cell's configuration, the
same readings bit for bit in every variant, and one compiled program per
chip whatever the depth.

The readings and compile counts come from one subprocess with two forced
host devices, so that layers cross devices as they do on the chips."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from chipbench.reference import gpt2 as base
from chipbench.reference import gpt2_per_chip as per_chip

ROOT = Path(__file__).resolve().parents[1]
CONF = json.loads((ROOT / "chipbench" / "configs" / "gpt2-xl-48.json")
                  .read_text())
VARIANTS = base.VARIANTS

SCRIPT = textwrap.dedent(r'''
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, numpy as np
    from chipbench import run
    from chipbench.reference import gpt2 as A, gpt2_per_chip as B
    from chipbench.traffic_gen import host_batches

    files = run.ROOT / "chipbench"
    conf = json.loads((files / "configs" / "gpt2-xl-48.json").read_text())
    traffic = json.loads((files / "traffic" / "gpipe4-adatopk.json")
                         .read_text())
    traffic.update(seq=32, distinct_batches=3, micro_batch=2, batch=8)
    small = dict(n_embd=64, n_head=2, n_positions=32, vocab_size=300)
    seed = 2**33 + 7
    host = host_batches(traffic, small["vocab_size"], seed)[:3]
    compiles = [0]

    def count(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count)

    def readings(mod, n_layer, variant):
        c = dict(conf, n_layer=n_layer, **small)
        edges = [{"after": f"block_{n_layer // 2 - 1}", "k_per_block": 14}]
        before = compiles[0]
        r = mod.Trainer(c, edges, 4096, traffic["optimizer"],
                        jax.devices()[:2], variant).run(seed, host, 3)
        return r, compiles[0] - before

    out = {"equal": {}, "compiles": {}}
    for variant in A.VARIANTS:
        a, _ = readings(A, 4, variant)
        b, _ = readings(B, 4, variant)
        out["equal"][variant] = (
            a.losses == b.losses and a.grad1 == b.grad1
            and a.delta == b.delta
            and all(np.array_equal(a.grad1_sample[n], b.grad1_sample[n])
                    for n in a.grad1)
            and all(np.array_equal(a.delta_sample[n], b.delta_sample[n])
                    for n in a.delta))
    for n_layer in (4, 8):
        jax.clear_caches()
        out["compiles"][n_layer] = readings(B, n_layer, "reference")[1]

    # bytes held on each device after every layer's backward, at most
    out["peak"] = {}
    for mod in (A, B):
        c = dict(conf, n_layer=8, **small)
        tr = mod.Trainer(c, [], 4096, traffic["optimizer"],
                         jax.devices()[:2])
        peak = {}

        def vjp(*args, _vjp=tr._block_vjp):
            got = jax.block_until_ready(_vjp(*args))
            held = {}
            for a in jax.live_arrays():
                for s in a.addressable_shards:
                    d = str(s.device)
                    held[d] = held.get(d, 0) + s.data.nbytes
            for d, n in held.items():
                peak[d] = max(peak.get(d, 0), n)
            return got

        tr._block_vjp = vjp
        tr.run(seed, host, 3)
        out["peak"][mod.__name__.rsplit(".", 1)[1]] = peak
    print("RESULT " + json.dumps(out))
''')


@pytest.fixture(scope="module")
def readings():
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "PYTHONPATH": f"{ROOT / 'src'}:{ROOT}", "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600, cwd=ROOT, env=env)
    line = next((l for l in res.stdout.splitlines()
                 if l.startswith("RESULT ")), None)
    assert line, res.stdout[-3000:] + res.stderr[-3000:]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("name", base.leaf_names(CONF, [0]))
def test_sample_positions_are_those_of_the_strided_slice(name):
    shape = base.leaf_shape(CONF, name)
    n = int(np.prod(shape))
    stride = max(1, n // base.SAMPLE)
    want = np.arange(0, n, stride)[:base.SAMPLE]
    got = np.ravel_multi_index(per_chip.sample_positions(shape), shape)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape", [(7,), (base.SAMPLE + 3,), (300, 451),
                                   (3, 5, 7)])
def test_sample_equals_the_strided_slice(shape):
    a = np.random.default_rng(0).standard_normal(shape).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(per_chip.sample(a)),
                                  np.asarray(base.sample(a)))


@pytest.mark.parametrize("variant", VARIANTS)
def test_readings_are_bit_equal_to_the_layer_keyed_reference(readings,
                                                             variant):
    assert readings["equal"][variant]


def test_each_chip_holds_what_it_holds_under_the_layer_keyed_reference(
        readings):
    """Each unit's weights and Adam state stay on the unit's device: no
    device holds more at any layer's backward than under ``gpt2.py``."""
    base_, mine = readings["peak"]["gpt2"], readings["peak"]["gpt2_per_chip"]
    assert set(mine) == set(base_) and len(mine) == 2
    for device, held in mine.items():
        assert held <= 1.1 * base_[device], (device, held, base_[device])


def test_programs_compile_once_per_chip_whatever_the_depth(readings):
    """Twice the layers on the same devices compile no program more."""
    counts = readings["compiles"]
    assert counts["4"] == counts["8"] > 0
