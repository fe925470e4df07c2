"""`correct` comes out false for the control and for each fault planted
underneath the timed path, and true for a sound run (CPU, small size).

The runs skip the harness's look for a chip (`require_chip=False`) and
drive everything else: set-up, the window, the reference and the
comparison with the cell's own limits."""
import io
import json
import time

import pytest

from bench.harness import runner
from bench.harness.spec import Spec
from bench.tests.conftest import TINY, make_root

CELLS = ('paper-cnn.k20.packed', 'paper-cnn.k20.analytic')
FAULTS = ('state_unchanged', 'half_batch', 'altered_answer')
SEED = 2 ** 31 + 12345


def _run(root, cell, fault=None):
    out, err = io.StringIO(), io.StringIO()
    rc = runner.run(cell, SEED, 0.5, False, time.time(), require_chip=False,
                    root=root, fault=fault, out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize('cell', CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    result = _run(tiny_root, cell)
    assert result['correct'], result['checks']
    assert list(result)[-1] == 'checks'


@pytest.mark.parametrize('fault', FAULTS)
@pytest.mark.parametrize('cell', CELLS)
def test_planted_fault_is_not_correct(tiny_root, cell, fault):
    result = _run(tiny_root, cell, fault)
    assert not result['correct'], result['checks']


@pytest.mark.parametrize('cell', CELLS)
def test_control_in_lower_precision_reads_farther_than_a_sound_run(
        tiny_root, cell):
    # The bfloat16 control does not fail the limits, here or at the
    # cells' size: the program's float32 products already run as one
    # bf16 pass on a TPU (PERF.md, Findings).  What holds is that it reads
    # farther from the reference than a sound run on the steady round-0
    # numbers.
    spec = Spec(cell, tiny_root)
    c = spec.adapter().Cell(spec.config, spec.traffic, SEED)
    control = c.control()
    control = c.readings(control, c.reference(control))
    _, sound = runner.first_segment(c)
    c.release()
    sound = c.readings(sound, c.reference(sound))
    for name in ('loss_gap.r0', 'objective_gap.r0'):
        assert control[name] > 3 * sound[name], (name, control, sound)


def test_objective_of_q_and_p_is_the_objective_of_alpha_and_beta():
    import numpy as np
    from bench.reference import allocation as A
    rng = np.random.default_rng(0)
    k = 6
    g2, gb2 = rng.uniform(1, 5, k), np.full(k, 2.0)
    fl = {'lipschitz': 20.0, 'learning_rate': 0.05, 'quant_bits': 3,
          'b0_bits': 64, 'bandwidth_hz': 1e7, 'noise_psd_w': 4e-21,
          'latency_s': 0.5}
    prob = A.Problem(g2, gb2, 0.5 * np.sqrt(g2 * gb2), 0.1 * g2,
                     rng.uniform(1e-8, 1e-6, k), np.full(k, 4e-4), 500, fl)
    alpha, beta = rng.uniform(0.2, 0.8, k), np.full(k, 1.0 / k)
    q, p = prob.success(alpha, beta)
    assert prob.objective_qp(q, p) == pytest.approx(
        prob.objective(alpha, beta), rel=1e-9)
