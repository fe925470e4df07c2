"""§IV-C complexity analysis — allocator wall-time vs device count K.

The K sweep is ONE ``stack_problems`` -> ``solve_batched`` dispatch per
method: the ragged K grid is zero-padded to the widest cohort (mask
semantics in core/README.md) and every per-point ``alloc_K{k}_{method}_
jax`` row is amortized out of that single grid solve, with the solver's
``iters_used`` riding the derived field.  The per-K host NumPy loop this
replaces (the old ``alloc_K{k}_{method}`` rows) survives only as the
timed reference behind the batched headline's extrapolated speedup.

``alloc_grid_{method}`` rows report the grid dispatch itself plus the
early-exit dividend: the same grid solved fixed-trip
(``early_exit=False``) over the identical iteration budget, so the
ratio isolates what convergence-aware ``lax.while_loop`` exits buy at
unchanged objectives.  The headline batched rows — ONE dispatch over a
block-fading trajectory of B draws vs the extrapolated host loop —
keep their ISSUE-5 shape and gain the same early-exit comparison.
BENCH_SMOKE=1 shrinks the K sweep and the batch.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from common import SMOKE, emit

import jax
import jax.numpy as jnp
from repro.configs.base import FLConfig
from repro.core import allocation as AL
from repro.core import allocation_jax as AJ
from repro.core import channel as CH


def rep_problem(k, seed=0, power_dbm=-25.0):
    """A representative eq. (28) problem at cohort size ``k`` — seeded
    stats in the ranges the FL loop produces (shared by the fig-7/fig-9
    sweep grids in bench_power/bench_devices)."""
    fl = FLConfig(tx_power_dbm=power_dbm)
    key = jax.random.PRNGKey(seed)
    d = CH.sample_distances(key, k, 500.0)
    gains = CH.path_gain(np.asarray(d), fl.path_loss_exp)
    p_w = np.full(k, fl.tx_power_w)
    rng = np.random.RandomState(seed)
    g2 = np.abs(rng.randn(k)) + 0.2
    gb2 = np.abs(rng.randn(k)) * 0.4 + 0.05
    v = np.sqrt(g2 * gb2) * rng.uniform(0, 1, k)
    d2 = np.abs(rng.randn(k)) * 0.05
    return AL.problem_from_stats(g2, gb2, v, d2, gains, p_w, 60000, fl)


_problem = rep_problem


def _iters(method):
    return 2 if method == 'alternating' else 6


def solve_grid(probs, method, max_iters, label, point_names):
    """ONE ``stack_problems`` -> ``solve_batched`` dispatch over a
    sweep: emits per-point rows (grid-amortized us_per_call, objective +
    ``iters_used`` derived) plus a ``{label}`` grid row whose derived
    field carries the early-exit speedup vs the SAME grid solved
    fixed-trip."""
    with jax.enable_x64():
        grid = AJ.stack_problems(probs)
    sol = AJ.solve_batched(grid, method, max_iters=max_iters)
    jax.block_until_ready(sol)                        # compile
    t0 = time.time()
    sol = AJ.solve_batched(grid, method, max_iters=max_iters)
    jax.block_until_ready(sol)
    dt = time.time() - t0
    ft = AJ.solve_batched(grid, method, max_iters=max_iters,
                          early_exit=False)
    jax.block_until_ready(ft)                         # compile
    t0 = time.time()
    ft = AJ.solve_batched(grid, method, max_iters=max_iters,
                          early_exit=False)
    jax.block_until_ready(ft)
    dt_ft = time.time() - t0
    objs = np.asarray(sol.objective)
    iters = np.asarray(sol.iters)
    reasons = np.asarray(sol.exit_reason)
    for i, name in enumerate(point_names):
        emit(name, 1e6 * dt / len(point_names),
             f'objective={objs[i]:.4f},iters_used={iters[i]}')
    emit(label, 1e6 * dt,
         f'early_exit_speedup={dt_ft / max(dt, 1e-9):.2f}x,'
         f'points={len(point_names)},'
         f'exit_converged={int(np.sum(reasons == AJ.EXIT_CONVERGED))}')
    return sol


def main() -> None:
    ks = (10, 20) if SMOKE else (10, 20, 40, 80)
    # full iteration budget for both methods: early exit leaves at the
    # relative-objective criterion, so a larger cap costs nothing once
    # converged (the fixed-trip comparison burns it in full)
    for method in ('alternating', 'barrier'):
        solve_grid([_problem(k) for k in ks], method, 6,
                   f'alloc_grid_{method}',
                   [f'alloc_K{k}_{method}_jax' for k in ks])

    # headline: one batched dispatch over a block-fading trajectory
    b = 8 if SMOKE else 64
    k = 8
    prob = _problem(k)
    with jax.enable_x64():
        fades = CH.block_fading_trajectory(
            jax.random.PRNGKey(1), jnp.asarray(prob.gains), b,
            rho=0.8, shadow_std_db=4.0)
        batched = AJ.batch_over_gains(AJ.from_reference(prob), fades)
    fades_np = np.asarray(fades, np.float64)
    for method in ('alternating', 'barrier'):
        sol = AJ.solve_batched(batched, method, max_iters=_iters(method))
        jax.block_until_ready(sol)                    # compile
        t0 = time.time()
        sol = AJ.solve_batched(batched, method, max_iters=_iters(method))
        jax.block_until_ready(sol)
        tb = time.time() - t0
        ft = AJ.solve_batched(batched, method, max_iters=_iters(method),
                              early_exit=False)
        jax.block_until_ready(ft)                     # compile
        t0 = time.time()
        ft = AJ.solve_batched(batched, method, max_iters=_iters(method),
                              early_exit=False)
        jax.block_until_ready(ft)
        tb_ft = time.time() - t0
        n_ref = 1 if SMOKE else (2 if method == 'alternating' else 6)
        t0 = time.time()
        for i in range(n_ref):
            AL.solve(dataclasses.replace(prob, gains=fades_np[i]),
                     method, max_iters=_iters(method))
        t_host = (time.time() - t0) / n_ref * b
        emit(f'alloc_jax_batched_B{b}_K{k}_{method}', 1e6 * tb,
             f'speedup={t_host / tb:.1f}x_vs_host_loop_extrap{n_ref},'
             f'early_exit_speedup={tb_ft / max(tb, 1e-9):.2f}x')


if __name__ == '__main__':
    main()
