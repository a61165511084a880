"""`mnist-mlp.grid-fedavg-q8`: sound, control and broken runs at a tiny size (see
`checkkit.py`)."""
import pytest

from bench import compare
from bench.tests import checkkit

NAME = "mnist-mlp.grid-fedavg-q8"


def test_sound_run_is_correct(monkeypatch):
    out = checkkit.run(NAME, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


def test_control_is_not_correct():
    nums, limits = checkkit.control_numbers(NAME)
    assert not compare.verdict(nums, limits), nums


@pytest.mark.parametrize("fault", checkkit.faults(NAME))
def test_broken_run_is_not_correct(fault, monkeypatch):
    out = checkkit.run(NAME, monkeypatch, fault)
    assert not out["correct"], out["checks"]
