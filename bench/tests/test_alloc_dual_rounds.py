"""`alloc_dual_rounds`: the mean tree rounds of the eq. (28) dual
bisection over the traced rounds that solved, read from the telemetry
ring; None on a program that does not record them."""
from types import SimpleNamespace

import pytest

from bench.harness.spec import ROOT, Spec


def _read(rows):
    ctx = SimpleNamespace(telemetry=list(rows))
    return Spec('paper-cnn.k20.packed', ROOT).metric_reader(
        'alloc_dual_rounds').read(ctx)


def _row(steps, rounds):
    return SimpleNamespace(alloc_steps=steps, alloc_dual_rounds=rounds)


def test_mean_over_rounds_that_solved():
    rows = [_row(0, 0), _row(960, 12), _row(1920, 30)]
    assert _read(rows) == pytest.approx(21.0)


def test_none_without_a_solved_round_or_the_field():
    assert _read([_row(0, 0)]) is None
    assert _read([SimpleNamespace(alloc_steps=960)]) is None
    assert _read([]) is None
