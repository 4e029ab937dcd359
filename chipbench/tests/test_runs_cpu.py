"""Whole runs of both paths at smoke widths on the CPU, past the look for a
chip: a sound run is correct, and each fault planted under the timed path
makes ``correct`` false.

The gpipe runs need four devices: the module sets
``--xla_force_host_platform_device_count=4`` when it is run alone, and
skips them where JAX started with fewer.
"""
import json
import os
from pathlib import Path

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import jax                                                       # noqa: E402
import jax.numpy as jnp                                          # noqa: E402
import pytest                                                    # noqa: E402

from chipbench import run                                        # noqa: E402

FILES = Path(__file__).resolve().parents[1]
SMALL = dict(n_layer=4, n_embd=128, n_head=4, n_positions=64,
             vocab_size=500)
LIMITS = json.loads((FILES / "limits" / "rad-adatopk.json").read_text())


def small_cell(kind: str) -> run.Cell:
    """gpt2-xl's files at smoke widths; blocks of 4096 still split every
    compressed tensor exactly (2 x 64 x 128 and 2 x 64 x 512)."""
    cfg = "gpt2-xl-rad12" if kind == "rad" else "gpt2-xl-gpipe48"
    traffic = "rad-adatopk" if kind == "rad" else "gpipe4-adatopk"
    conf = json.loads((FILES / "configs" / f"{cfg}.json").read_text())
    conf.update(SMALL, name=f"small-{kind}")
    t = json.loads((FILES / "traffic" / f"{traffic}.json").read_text())
    t.update(seq=64, distinct_batches=4)
    if kind == "rad":
        # what AdaTopK plans on testbed 1 at these widths
        t.update(batch=2, compressed_edges=[
            {"after": a, "k_per_block": k} for a, k in
            [("embed", 28), ("block_0", 28), ("block_1", 28),
             ("block_2", 28), ("block_3", 27), ("head", 14)]])
    else:
        t.update(micro_batch=2, batch=8, compressed_edges=[
            {"after": "block_1", "k_per_block": 14}])
    e2e = [{"name": n, "unit": u} for n, u in
           (("tokens_per_s", "tokens/s"), ("step_hbm_gb", "GB"),
            ("setup_s", "s"))]
    return run.Cell(f"small-{kind}", 1 if kind == "rad" else 4, conf, t,
                    LIMITS, e2e, [])


def devices(cell):
    if len(jax.devices()) < cell.chips:
        pytest.skip(f"needs {cell.chips} devices")
    return jax.devices()[:cell.chips]


def execute(kind, seed=2**31 + 5):
    cell = small_cell(kind)
    return run.execute(cell, seed, 0.5, False, devices(cell))


@pytest.mark.parametrize("kind", ["rad", "gpipe"])
def test_sound_run_is_correct(kind):
    res = execute(kind)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "step_hbm_gb", "setup_s"}


def test_rad_run_with_interpreted_kernels(monkeypatch):
    """The codec as Pallas kernels (interpret mode) under the RAD path."""
    from repro.kernels import ops
    resolve = ops.resolve_policy
    monkeypatch.setattr(ops, "resolve_policy", lambda p: "interpret"
                        if p == "auto" else resolve(p))
    res = execute("rad")
    assert res["correct"], res["checks"]


def _frozen_adamw(*args, **kwargs):
    from repro.optim.optimizers import Optimizer, adamw
    opt = adamw(*args, **kwargs)
    return Optimizer(init=opt.init, update=lambda g, s, p: (p, s))


def _half_batch_ce(logits, labels, *args, **kwargs):
    from repro.models.layers import cross_entropy
    half = logits.shape[0] // 2
    return cross_entropy(logits[:half], labels[:half], *args, **kwargs)


@pytest.mark.parametrize("kind", ["rad", "gpipe"])
def test_state_left_unchanged_is_caught(kind, monkeypatch):
    import repro.optim
    monkeypatch.setattr(repro.optim, "adamw", _frozen_adamw)
    res = execute(kind)
    assert not res["correct"]
    assert res["checks"]["delta_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("kind,module", [
    ("rad", "repro.models.opgraph_models"),
    ("gpipe", "repro.distributed.pipeline")])
def test_half_the_batch_is_caught(kind, module, monkeypatch):
    import importlib
    monkeypatch.setattr(importlib.import_module(module), "cross_entropy",
                        _half_batch_ce)
    res = execute(kind)
    assert not res["correct"]


def test_exchange_left_out_is_caught(monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: x)
    res = execute("gpipe")
    assert not res["correct"]


@pytest.mark.parametrize("kind,variant,cell_limits", [
    ("rad", "half_batch", "rad-adatopk"), ("rad", "half_batch", "rad-dense"),
    ("gpipe", "half_batch", "rad-adatopk"),
    ("gpipe", "no_exchange", "rad-adatopk")])
def test_faults_in_the_programs_place_are_not_correct(kind, variant,
                                                      cell_limits):
    """The reference with a fault planted, put in the program's place, fails
    the limits of a cell of that path."""
    cell = small_cell(kind)
    cell.limits = json.loads(
        (FILES / "limits" / f"{cell_limits}.json").read_text())
    devs = devices(cell)
    from chipbench.traffic_gen import host_batches
    batches = host_batches(cell.traffic, cell.conf["vocab_size"], 7)[:3]
    ref = run.reference(cell, devs, 7, batches)
    bad = run.reference(cell, devs, 7, batches, variant)
    checks = run.compare(cell, bad, ref, [])
    assert not all(c["ok"] for c in checks.values()), checks


def test_reference_topk_keeps_the_lower_index_on_ties():
    from chipbench.reference.gpt2 import topk_blocks
    x = jnp.array([1.0, -3.0, 3.0, 0.5, 3.0, -2.0, 0.0, 1.0])
    got = topk_blocks(x, 2, 4)
    assert got.tolist() == [0.0, -3.0, 3.0, 0.0, 3.0, -2.0, 0.0, 0.0]


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    """The traced branch end to end. A CPU has no entry in the peaks table
    and no TPU plane in its trace: the test lends it peaks, and the
    readers of device planes find nothing and report nothing."""
    from chipbench import arith
    monkeypatch.setattr(arith, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    cell = small_cell("rad")
    cell.per_layer = [{"name": n, "unit": u} for n, u in
                      (("step_mfu", "%"), ("compile_s", "s"),
                       ("device_idle_share", "%"))]
    res = run.execute(cell, 3, 0.5, True, devices(cell))
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"step_mfu", "compile_s"}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
