"""The numbers that decide ``correct``, on hand-made readings."""
import numpy as np
import pytest

from chipbench.compare import checks, numbers
from chipbench.reference.gpt2 import Readings


def readings(noise=0.0, leaf="b", seed=0):
    rng = np.random.default_rng(seed)
    base = {n: np.random.default_rng(i).normal(size=1000) * (i + 1)
            for i, n in enumerate("abc")}
    norms = {n: float(np.linalg.norm(a)) for n, a in base.items()}
    if noise:       # noise on the elements only: the norms stay as they are
        base[leaf] = base[leaf] + noise * np.std(base[leaf]) * rng.normal(
            size=1000)
    return Readings([2.0, 1.5, 1.0], norms, dict(norms), base, dict(base))


def test_equal_readings_read_nought():
    got = numbers(readings(), readings())
    assert all(v == 0.0 for v, _ in got.values())


@pytest.mark.parametrize("noise", [1e-3, 1e-2, 1e-1])
def test_element_gap_reads_the_noise_a_norm_averages_away(noise):
    ref, prog = readings(), readings(noise=noise)
    got = numbers(prog, ref)
    assert got["grad_elem_gap"][0] == pytest.approx(noise, rel=0.15)
    assert got["grad_elem_gap"][1] == "b"
    assert got["grad_gap"][0] == 0.0


def test_a_nan_fails_its_limit():
    prog = readings()
    prog.grad1_sample["a"] = np.full(1000, np.nan)
    out = checks(numbers(prog, readings()), {"grad_elem_gap": 1.0})
    assert not out["grad_elem_gap"]["ok"]


def test_limits_lie_between_the_sound_runs_and_the_control():
    from chipbench.set_limits import STEADY, limits

    def line(side, scale):
        return {"side": side, "precision": "default",
                **{n: scale * 1e-3 for n in STEADY}}
    lines = ([line("program", s) for s in (0.8, 1.0, 0.9)]
             + [line("bf16", s) for s in (9.0, 8.0)]
             + [line("half_batch", 50.0)])
    got, caught = limits(lines, "default", STEADY)
    assert caught
    for r in got.values():
        assert r["lower"] < r["limit"] < r["upper"]
    assert got["grad_elem_median"]["from"] == "control"
    assert got["grad_elem_median"]["upper"] == pytest.approx(8e-3)
