"""NumPy<->JAX allocation-engine parity + property wall (ISSUE 5).

The contract under test (documented in src/repro/core/README.md):

* parity — both engines consume the same closed forms
  (repro.core.alloc_common) in float64 and differ only in control-flow
  bookkeeping and libm ulps, so the contractive SCA path agrees to
  ~1e-11 relative on objectives / ~1e-6 on iterates, while the barrier
  path's long PGD chains are path-chaotic and agree to the solvers'
  convergence tol instead (see TOL below);
* batching — ``solve_batched`` is bit-identical to a Python loop of
  single jitted solves (the engine pins every reduction order, see
  ``allocation_jax._ordered_sum``);
* invariants — alpha in [0, alpha_max], beta strictly inside (0, 1) on
  the bandwidth simplex, q >= p wherever the modulus channel binds
  (sign prioritization; in the saturated regime q ~ p ~ 1 the solver is
  indifferent and q - p can dip ~1e-4 below zero), and the alternating
  objective is monotone non-increasing per outer iteration (the barrier
  variant's interior-penalty steps do not guarantee true-objective
  descent per iteration — only the final uniform safeguard).
"""
import dataclasses

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.base import FLConfig
from repro.core import allocation as AL
from repro.core import allocation_jax as AJ
from repro.core import channel as CH

# documented engine-parity tolerances (src/repro/core/README.md).  The
# SCA path is contractive, so cross-library libm ulps stay ulps; the
# barrier path runs ~1000 sequential PGD steps with discrete
# backtracking decisions, so the engines approach the same basin along
# different trajectories — endpoint spread is bounded by the solvers'
# convergence tol, not by ulps.
TOL = {
    'alternating': dict(obj_rtol=1e-8, ab_atol=1e-4, qp_atol=1e-6),
    'barrier': dict(obj_rtol=2e-5, ab_atol=5e-3, qp_atol=1e-4),
}
TOL['uniform'] = TOL['alternating']
# q >= p is asserted where the modulus channel binds
P_BINDING = 0.99


def _problem(k=8, power_dbm=-14.0, seed=0, dim=60000,
             gains=None) -> AL.AllocationProblem:
    fl = dataclasses.replace(FLConfig(), tx_power_dbm=power_dbm)
    if gains is None:
        key = jax.random.PRNGKey(seed)
        d = CH.sample_distances(key, k, 500.0)
        gains = CH.path_gain(np.asarray(d), fl.path_loss_exp)
    p_w = np.full(k, fl.tx_power_w)
    rng = np.random.RandomState(seed)
    g2 = np.abs(rng.randn(k)) + 0.2
    gb2 = np.abs(rng.randn(k)) * 0.4 + 0.05
    v = np.sqrt(g2 * gb2) * rng.uniform(0, 1, k)
    d2 = np.abs(rng.randn(k)) * 0.05
    return AL.problem_from_stats(g2, gb2, v, d2, gains, p_w, dim, fl)


def _assert_parity(ref: AL.Allocation, got: AL.Allocation, method: str):
    tol = TOL[method]
    assert got.objective == pytest.approx(ref.objective,
                                          rel=tol['obj_rtol'], abs=1e-12)
    np.testing.assert_allclose(got.alpha, ref.alpha, atol=tol['ab_atol'])
    np.testing.assert_allclose(got.beta, ref.beta, atol=tol['ab_atol'])
    np.testing.assert_allclose(got.q, ref.q, atol=tol['qp_atol'])
    np.testing.assert_allclose(got.p, ref.p, atol=tol['qp_atol'])


# ---------------------------------------------------------------------------
# NumPy <-> JAX parity grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('k', [4, 8, 32])
@pytest.mark.parametrize('power', [-4.0, -14.0, -24.0])
def test_parity_barrier_grid(k, power):
    prob = _problem(k=k, power_dbm=power, seed=k)
    _assert_parity(AL.solve(prob, 'barrier'), AJ.solve(prob, 'barrier'),
                   'barrier')


@pytest.mark.parametrize('k', [4, 8, 32])
@pytest.mark.parametrize('power', [-6.0, -20.0])
def test_parity_alternating_grid(k, power):
    # max_iters=2 matches the reference's host-cost-bound FL-loop setting
    prob = _problem(k=k, power_dbm=power, seed=k + 1)
    _assert_parity(AL.solve(prob, 'alternating', max_iters=2),
                   AJ.solve(prob, 'alternating', max_iters=2),
                   'alternating')


def test_uniform_method_parity():
    prob = _problem(k=8, power_dbm=-18.0, seed=5)
    _assert_parity(AL.solve(prob, 'uniform'), AJ.solve(prob, 'uniform'),
                   'uniform')


# ---------------------------------------------------------------------------
# batching: vmapped solve ==(bit)== loop of single solves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('method', ['alternating', 'barrier'])
def test_vmap_batch_bit_matches_single_solves(method):
    probs = [_problem(k=6, power_dbm=p, seed=s)
             for s, p in enumerate([-4.0, -10.0, -16.0, -22.0, -28.0,
                                    -34.0, -8.0, -19.0])]
    with jax.enable_x64():
        batched = AJ.stack_problems(probs)
    sol = AJ.solve_batched(batched, method, max_iters=3)
    for i, prob in enumerate(probs):
        with jax.enable_x64():
            one = AJ._solve_jit(AJ.from_reference(prob), method=method,
                                max_iters=3)
        for f in ('alpha', 'beta', 'q', 'p', 'objective', 'iters'):
            a = np.asarray(getattr(sol, f)[i])
            b = np.asarray(getattr(one, f))
            assert np.array_equal(a, b), (method, i, f)


def test_batch_over_gains_shapes():
    prob = _problem(k=4, power_dbm=-20.0, seed=9)
    with jax.enable_x64():
        jp = AJ.from_reference(prob)
        fades = CH.block_fading_trajectory(jax.random.PRNGKey(0),
                                           prob.gains, 12)
        batched = AJ.batch_over_gains(jp, fades)
    assert batched.gains.shape == (12, 4)
    assert batched.A.shape == (12, 4)
    sol = AJ.solve_batched(batched, 'barrier')
    assert sol.alpha.shape == (12, 4)
    assert bool(np.all(np.isfinite(np.asarray(sol.objective))))


@pytest.mark.slow
def test_batched_solve_matches_numpy_reference_over_64_fading_draws():
    """Acceptance: one solve_batched dispatch over >= 64 fading draws
    matches the NumPy reference per-draw within the documented
    tolerance."""
    base = _problem(k=8, power_dbm=-16.0, seed=2)
    with jax.enable_x64():
        fades = CH.block_fading_trajectory(jax.random.PRNGKey(7),
                                           base.gains, 64, rho=0.8,
                                           shadow_std_db=4.0)
    fades = np.asarray(fades, np.float64)
    probs = [dataclasses.replace(base, gains=fades[i]) for i in range(64)]
    with jax.enable_x64():
        sol = AJ.solve_batched(AJ.stack_problems(probs), 'barrier')
    for i, prob in enumerate(probs):
        ref = AL.solve(prob, 'barrier')
        tol = TOL['barrier']
        assert float(sol.objective[i]) == pytest.approx(
            ref.objective, rel=tol['obj_rtol'], abs=1e-12), i
        np.testing.assert_allclose(np.asarray(sol.alpha[i]), ref.alpha,
                                   atol=tol['ab_atol'])
        np.testing.assert_allclose(np.asarray(sol.beta[i]), ref.beta,
                                   atol=tol['ab_atol'])
        np.testing.assert_allclose(np.asarray(sol.q[i]), ref.q,
                                   atol=tol['qp_atol'])
        np.testing.assert_allclose(np.asarray(sol.p[i]), ref.p,
                                   atol=tol['qp_atol'])


# ---------------------------------------------------------------------------
# float32 trace parity (the fused-round in-trace solve, ISSUE 7)
# ---------------------------------------------------------------------------
#
# The fused lax.scan round solves eq. (28) in float32 INSIDE the round
# trace (f64 only exists behind the enable_x64 host wrappers).  The f32
# caps (allocation_jax._caps: exp/pow/log saturation + the wider alpha
# boundary clip a_eps=1e-6 — 1 - 1e-12 rounds to exactly 1.0 in f32 and
# NaN-ed the barrier gradient via 0*inf) keep every iterate finite; the
# contract below binds on what the round actually consumes (objective,
# q, p).  alpha/beta are checked loosely only: near-flat objective
# regions make the argmin tie-break precision-sensitive (measured worst
# drift over the K x SNR x method grid: dalpha ~2e-2 at obj_rel ~2e-7).
F32_TOL = dict(obj_rtol=1e-4,      # measured worst 1.9e-5
               qp_atol=5e-3,       # measured worst dq 2.2e-4, dp 1.1e-3
               ab_atol=5e-2)       # argmin ties on flat objectives


@pytest.mark.parametrize('method', ['alternating', 'barrier'])
@pytest.mark.parametrize('k', [4, 8, 32])
@pytest.mark.parametrize('power', [-4.0, -14.0, -24.0, -34.0])
def test_f32_trace_parity_grid(method, k, power):
    prob = _problem(k=k, power_dbm=power, seed=k + int(-power))
    ref = AL.solve(prob, method, max_iters=3)
    jp32 = AJ.from_reference(prob, dtype=jax.numpy.float32)
    sol = jax.jit(AJ.solve_traceable,
                  static_argnames=('method', 'max_iters'))(
        jp32, method, max_iters=3)
    q = np.asarray(sol.q)
    p = np.asarray(sol.p)
    obj = float(sol.objective)
    # every f32 iterate must stay finite (the K=32 / -4 dBm barrier cell
    # NaN-ed before the a_eps fix)
    assert np.isfinite(obj), (method, k, power)
    assert np.all(np.isfinite(q)) and np.all(np.isfinite(p))
    assert obj == pytest.approx(ref.objective, rel=F32_TOL['obj_rtol'],
                                abs=1e-10)
    np.testing.assert_allclose(q, ref.q, atol=F32_TOL['qp_atol'])
    np.testing.assert_allclose(p, ref.p, atol=F32_TOL['qp_atol'])
    np.testing.assert_allclose(np.asarray(sol.alpha), ref.alpha,
                               atol=F32_TOL['ab_atol'])
    np.testing.assert_allclose(np.asarray(sol.beta), ref.beta,
                               atol=F32_TOL['ab_atol'])


# ---------------------------------------------------------------------------
# allocation invariants (seeded grid — runs without hypothesis too)
# ---------------------------------------------------------------------------

def _check_invariants(sol: AL.Allocation, fl: FLConfig, method: str):
    assert np.all(sol.alpha >= -1e-12)
    assert np.all(sol.alpha <= min(max(fl.alpha_max, 1e-3), 1.0) + 1e-9)
    assert np.all(sol.beta > 0) and np.all(sol.beta < 1)
    assert sol.beta.sum() <= 1.0 + 1e-9
    assert np.all((sol.q >= 0) & (sol.q <= 1))
    assert np.all((sol.p >= 0) & (sol.p <= 1))
    # sign prioritization: q >= p wherever the modulus channel binds
    binding = sol.p <= P_BINDING
    assert np.all(sol.q[binding] >= sol.p[binding] - 1e-7), \
        (sol.q, sol.p, sol.alpha)
    if method == 'alternating':
        objs = sol.info['objectives']
        for a, b in zip(objs, objs[1:]):
            assert b <= a + 1e-9 * (1.0 + abs(a)), objs


@pytest.mark.parametrize('method', ['alternating', 'barrier'])
def test_invariants_seeded_grid_jax(method):
    for k, power, seed in [(4, -6.0, 11), (6, -18.0, 12), (8, -30.0, 13),
                           (6, -33.0, 14)]:
        prob = _problem(k=k, power_dbm=power, seed=seed)
        sol = AJ.solve(prob, method, max_iters=4)
        _check_invariants(sol, prob.fl, method)


def test_invariants_seeded_grid_numpy():
    for k, power, seed in [(4, -6.0, 11), (6, -18.0, 12), (8, -30.0, 13)]:
        prob = _problem(k=k, power_dbm=power, seed=seed)
        _check_invariants(AL.solve(prob, 'barrier'), prob.fl, 'barrier')
    prob = _problem(k=6, power_dbm=-20.0, seed=15)
    _check_invariants(AL.solve(prob, 'alternating', max_iters=3), prob.fl,
                      'alternating')


# ---------------------------------------------------------------------------
# hypothesis property wall (skips cleanly when hypothesis is absent)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 500), power=st.floats(-35.0, -2.0),
       k=st.sampled_from([4, 6, 8]))
def test_property_invariants_jax_alternating(seed, power, k):
    prob = _problem(k=k, power_dbm=power, seed=seed)
    sol = AJ.solve(prob, 'alternating', max_iters=3)
    _check_invariants(sol, prob.fl, 'alternating')


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 500), power=st.floats(-35.0, -2.0),
       k=st.sampled_from([4, 6, 8]))
def test_property_invariants_jax_barrier(seed, power, k):
    prob = _problem(k=k, power_dbm=power, seed=seed)
    _check_invariants(AJ.solve(prob, 'barrier'), prob.fl, 'barrier')


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 500), power=st.floats(-35.0, -2.0))
def test_property_invariants_numpy_barrier(seed, power):
    prob = _problem(k=6, power_dbm=power, seed=seed)
    _check_invariants(AL.solve(prob, 'barrier'), prob.fl, 'barrier')


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 500), power=st.floats(-35.0, -2.0))
def test_property_engines_agree(seed, power):
    """The two backends land on the same optimum for random instances."""
    prob = _problem(k=6, power_dbm=power, seed=seed)
    _assert_parity(AL.solve(prob, 'barrier'), AJ.solve(prob, 'barrier'),
                   'barrier')


# ---------------------------------------------------------------------------
# convergence-aware early exit (ISSUE 8)
# ---------------------------------------------------------------------------
#
# The early-exit lowering replaces the fixed-trip fori loops with
# bounded-trip while loops whose predicates are the done flags the
# fixed-trip bodies already used to freeze their carries — leaving the
# loop where the flag fires consumes the same final carry, so the
# default (inner_tol=0) early-exit solve is BIT-identical to the
# fixed-trip one, not merely within the parity tolerance.  inner_tol>0
# unlocks the tolerance-bounded inner exits (golden width / dual
# bisection / barrier displacement) and is bounded by the documented
# contract instead.

def _bits_equal(a: AL.Allocation, b: AL.Allocation):
    for f in ('alpha', 'beta', 'q', 'p'):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert a.objective == b.objective


@pytest.mark.parametrize('method', ['alternating', 'barrier'])
@pytest.mark.parametrize('k', [4, 8, 32])
@pytest.mark.parametrize('power', [-6.0, -24.0])
def test_early_exit_bit_matches_fixed_trip_grid(method, k, power):
    prob = _problem(k=k, power_dbm=power, seed=k + 2)
    ee = AJ.solve(prob, method, max_iters=3, early_exit=True)
    ft = AJ.solve(prob, method, max_iters=3, early_exit=False)
    _bits_equal(ee, ft)
    assert ee.info['iters_used'] == ft.info['iters_used']
    assert ee.info['exit_reason'] == ft.info['exit_reason']


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 500), power=st.floats(-35.0, -2.0),
       k=st.sampled_from([4, 6]))
def test_property_early_exit_bit_matches_fixed_trip(seed, power, k):
    prob = _problem(k=k, power_dbm=power, seed=seed)
    _bits_equal(AJ.solve(prob, 'alternating', max_iters=3,
                         early_exit=True),
                AJ.solve(prob, 'alternating', max_iters=3,
                         early_exit=False))


@pytest.mark.parametrize('method', ['alternating', 'barrier'])
def test_vmap_batch_early_exit_bit_matches_single_solves(method):
    """Batched early exit composes with vmap: the lowered while_loop
    steps until every element's predicate clears, select-freezing the
    finished ones — still bit-identical to single early-exit solves."""
    probs = [_problem(k=6, power_dbm=p, seed=s)
             for s, p in enumerate([-4.0, -16.0, -28.0, -8.0])]
    with jax.enable_x64():
        batched = AJ.stack_problems(probs)
    sol = AJ.solve_batched(batched, method, max_iters=3, early_exit=True)
    for i, prob in enumerate(probs):
        with jax.enable_x64():
            one = AJ._solve_jit(AJ.from_reference(prob), method=method,
                                max_iters=3, early_exit=True)
        for f in ('alpha', 'beta', 'q', 'p', 'objective', 'iters',
                  'exit_reason'):
            a = np.asarray(getattr(sol, f)[i])
            b = np.asarray(getattr(one, f))
            assert np.array_equal(a, b), (method, i, f)


@pytest.mark.parametrize('method', ['alternating', 'barrier'])
def test_ragged_stack_padded_solve_matches_unpadded(method):
    """Heterogeneous cohort sizes in one dispatch: zero-coefficient pads
    contribute exactly +0.0 to every masked ordered sum, so the real
    clients' solution is bit-identical to the unpadded single solve."""
    probs = [_problem(k=4, power_dbm=-10.0, seed=21),
             _problem(k=8, power_dbm=-22.0, seed=22)]
    with jax.enable_x64():
        batched = AJ.stack_problems(probs)
    assert batched.mask is not None and batched.A.shape == (2, 8)
    np.testing.assert_array_equal(
        np.asarray(batched.mask),
        [[1, 1, 1, 1, 0, 0, 0, 0], [1] * 8])
    sol = AJ.solve_batched(batched, method, max_iters=3)
    for i, prob in enumerate(probs):
        k = prob.n
        one = AJ.solve(prob, method, max_iters=3)
        for f in ('alpha', 'beta', 'q', 'p'):
            np.testing.assert_array_equal(
                np.asarray(getattr(sol, f)[i][:k]), getattr(one, f),
                err_msg=(method, i, f))
        assert float(sol.objective[i]) == one.objective, (method, i)


def test_exit_reason_and_iters_semantics():
    prob = _problem(k=6, power_dbm=-18.0, seed=31)
    # uniform never iterates and always "converges"
    u = AJ.solve(prob, 'uniform')
    assert u.info['iters_used'] == 0
    assert u.info['exit_reason'] == AJ.EXIT_CONVERGED
    # a generous budget converges before the cap
    sol = AJ.solve(prob, 'alternating', max_iters=8)
    assert 0 < sol.info['iters_used'] < 8
    assert sol.info['exit_reason'] == AJ.EXIT_CONVERGED
    # a 1-iteration budget cannot satisfy |prev - obj| with prev = inf
    capped = AJ.solve(prob, 'alternating', max_iters=1)
    assert capped.info['iters_used'] == 1
    assert capped.info['exit_reason'] in (AJ.EXIT_ITER_CAP,
                                          AJ.EXIT_UNIFORM_FALLBACK)
    # the NumPy reference mirrors the schema (same EXIT_* codes)
    ref = AL.solve(prob, 'alternating', max_iters=8)
    assert ref.info['iters_used'] == sol.info['iters_used']
    assert ref.info['exit_reason'] == AJ.EXIT_CONVERGED


@pytest.mark.parametrize('method', ['alternating', 'barrier'])
def test_inner_tol_frontier_within_contract(method):
    """inner_tol > 0 unlocks the tolerance-bounded inner exits (golden
    width / dual bisection / barrier displacement); the endpoint drift
    is bounded by the documented parity contract for the method."""
    tol = TOL[method]
    cut = []
    for k, power, seed in [(4, -8.0, 41), (8, -26.0, 42)]:
        prob = _problem(k=k, power_dbm=power, seed=seed)
        exact = AJ.solve(prob, method, max_iters=3, inner_tol=0.0)
        fast = AJ.solve(prob, method, max_iters=3, inner_tol=1e-6)
        # the tolerance exits never add counted work, and cut it here
        assert 0 < fast.info['steps'] <= exact.info['steps']
        cut.append(fast.info['steps'] < exact.info['steps'])
        assert fast.objective == pytest.approx(
            exact.objective, rel=tol['obj_rtol'], abs=1e-12)
        np.testing.assert_allclose(fast.q, exact.q, atol=tol['qp_atol'])
        np.testing.assert_allclose(fast.p, exact.p, atol=tol['qp_atol'])
    assert any(cut)


# ---------------------------------------------------------------------------
# solver step counter (JaxAllocation.steps -> RoundTelemetry.alloc_steps)
# ---------------------------------------------------------------------------

# the duals the bracket growth tries: 1, 10, ..., 1e29 (30 trips at most)
_GROW_DUALS = [0.0] + [10.0 ** i for i in range(30)]


@jax.jit
def _one_sca_round(prob, alpha, beta, duals):
    """One SCA round from ``beta``: its step count, and the bandwidth sum
    of the free search (the one ``optimize_beta_sca`` runs) at each of
    ``duals``."""
    surrogate = AJ._surrogate(prob, AJ._caps(prob.A.dtype), alpha, beta)

    def total(lam):
        b, _ = AJ._golden_vec(lambda x: surrogate(x) + lam * x,
                              beta.shape, beta.dtype)
        return AJ._msum(prob, b)

    steps = AJ.optimize_beta_sca(prob, alpha, beta, sca_rounds=1)[1]
    return steps, jax.vmap(total)(duals)


def _sca_searches_by_hand(totals):
    """Golden-section searches one SCA round runs, counted on the host
    from the searches' own answers (``totals`` at ``_GROW_DUALS``): the
    free search, and when its bandwidths overshoot the simplex, one
    search per dual bracket growth (x10 from 1 while the sum stays
    infeasible, 30 at most), one per bisection step and the final one."""
    if totals[0] <= 1.0:
        return 1
    grows = 0
    for hi, t in zip(_GROW_DUALS[1:], totals[1:]):
        grows += 1
        if not (t > 1.0 and hi < 1e30):
            break
    return 1 + grows + AJ.BISECTION_ITERS + 1


@pytest.mark.parametrize('k,latency,searches', [(1, None, 1),
                                                (4, None, 63),
                                                (4, 0.002, 92)])
def test_alloc_steps_hand_count_one_sca_round(k, latency, searches):
    """One SCA round's step count is GOLDEN_ITERS per search, with the
    searches counted by hand: a problem whose free search fits the
    simplex (K=1), one that binds after one bracket growth, and one whose
    bracket grows to its 30-trip cap (a latency budget tight enough that
    every dual below 1e29 leaves the sum infeasible)."""
    prob = _problem(k=k, power_dbm=-14.0, seed=3)
    if latency is not None:
        prob = dataclasses.replace(
            prob, fl=dataclasses.replace(prob.fl, latency_s=latency))
    with jax.enable_x64():
        jp = AJ.from_reference(prob)
        beta = jax.numpy.full((k,), 1.0 / k)
        alpha = jax.numpy.full((k,), 0.5)
        steps, totals = _one_sca_round(
            jp, alpha, beta, jax.numpy.asarray(_GROW_DUALS))
        assert _sca_searches_by_hand(np.asarray(totals)) == searches
        assert int(steps) == searches * AJ.GOLDEN_ITERS


@pytest.mark.parametrize('method', ['alternating', 'barrier'])
def test_alloc_steps_lowering_invariant_and_zero_on_uniform(method):
    """The whole solve's count is the same under the early-exit and the
    fixed-trip lowering (frozen trips are not counted) and a whole number
    of units: GOLDEN_ITERS per search for ``alternating``, one descent
    step and its BACKTRACK_ITERS halvings for ``barrier``.  ``uniform``
    counts 0."""
    unit = (AJ.GOLDEN_ITERS if method == 'alternating'
            else 1 + AJ.BACKTRACK_ITERS)
    for power, seed in [(-8.0, 41), (-24.0, 6)]:
        prob = _problem(k=4, power_dbm=power, seed=seed)
        ee = AJ.solve(prob, method, max_iters=3, early_exit=True)
        ft = AJ.solve(prob, method, max_iters=3, early_exit=False)
        assert ee.info['steps'] == ft.info['steps'] > 0
        assert ee.info['steps'] % unit == 0
    assert AJ.solve(prob, 'uniform').info['steps'] == 0
