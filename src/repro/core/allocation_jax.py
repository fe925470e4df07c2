"""JAX-native batched allocation engine — eq. (28) as one jitted dispatch.

``repro.core.allocation`` (the retained reference) solves the
hierarchical bandwidth/power problem in host-side float64 NumPy, which
puts a jit barrier and a device->host sync in the middle of every FL
round.  This module is the same Algorithm 1 — grid-bracketed +
safeguarded-Newton ``optimize_alpha`` (Lemma 3), SCA/majorize-minimize
``optimize_beta_sca`` with per-client golden-section under dual
bisection on the sum-bandwidth constraint, and the §IV-D log-barrier
fallback — rebuilt on ``lax.fori_loop``/``lax.cond`` fixed-trip control
flow over an :class:`JaxAllocationProblem` pytree, so that

* ``solve_traceable`` can be inlined into a jitted per-round pipeline
  (no host round-trip: ``fl_loop`` with ``allocation_backend='jax'``),
* ``solve_batched`` vmaps the whole solver over a leading batch axis —
  one dispatch solves allocations for an entire block-fading trajectory
  or an SNR x K scenario grid.

Control flow is masked AND convergence-aware: every early ``break`` of
the reference becomes a frozen carry under a ``done`` flag with the
same trip-count bounds, so the two engines walk the same iterates —
and by default (``early_exit=True``) the loops are bounded-trip
``lax.while_loop``s that stop at the exact iteration the ``done`` flag
fires instead of burning the remaining budget on frozen no-op trips.
Because every post-``done`` iteration of the fixed-trip form is a
frozen carry, the early exit is *bit-identical* to the fixed-trip
solve (``tests/test_allocation_jax.py`` pins this), composes with vmap
(XLA's batched ``while_loop`` freezes each converged element's carry
via select until the whole batch converges — exactly the masked
all-converged predicate), and stays compilable inside ``lax.scan``
(the predicate always includes the hard trip cap).  ``inner_tol > 0``
additionally enables tolerance-bounded exits of the golden-section /
dual-bisection / barrier-descent inner loops (interval width resp.
iterate displacement below ``inner_tol``) — faster but no longer
bit-identical; the measured accuracy-vs-wall-time frontier lives in
``src/repro/core/README.md``.

Ragged cohorts batch through zero-padding: ``stack_problems`` with
heterogeneous K pads every per-client leaf to the widest cohort and
sets the optional ``mask`` field (1 real / 0 pad).  Padded entries
carry zero eq. (27) coefficients, so they contribute exactly ``+0.0``
to every ordered reduction — real-client trajectories are bit-identical
to the unpadded solve.

Precision contract (documented in ``src/repro/core/README.md``): the
closed forms (shared with the reference via ``repro.core.alloc_common``)
need float64 — the f64 guard constants ``EXP_CAP=600`` / ``POW_CAP=500``
/ ``H_FLOOR=-1e150`` all overflow float32.  The host-facing wrappers
(``solve``, ``solve_batched``) therefore run under
``jax.enable_x64`` and match the NumPy reference to tight
tolerances; ``solve_traceable`` embedded in an f32 program instead
substitutes f32-safe caps (``_caps``) and keeps the same argmin
structure at reduced precision.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import FLConfig
from repro.core import alloc_common as AC
from repro.core.allocation import Allocation, AllocationProblem


class JaxAllocationProblem(NamedTuple):
    """Eq. (28) instance as a pytree of arrays (vmap-able over a leading
    batch axis; the trailing axis of the per-client fields is K)."""
    A: jax.Array                 # (..., K) eq. (27) coefficients
    B: jax.Array
    C: jax.Array
    D: jax.Array
    gains: jax.Array             # (..., K) large-scale channel gains
    p_w: jax.Array               # (..., K) power budgets
    sign_bits: jax.Array         # (...,)  l
    mod_bits: jax.Array          # (...,)  l*b + b0
    bandwidth_hz: jax.Array      # (...,)  B
    noise_psd_w: jax.Array       # (...,)  N0 (W/Hz)
    latency_s: jax.Array         # (...,)  tau
    alpha_max: jax.Array         # (...,)  cap on the sign power share
    mask: Optional[jax.Array] = None  # (..., K) 1.0 real / 0.0 zero-pad
    #   (ragged-K batching; None — the common case — vanishes from the
    #   pytree, keeping unpadded problems bit- and cache-compatible)


# exit reasons reported by ``solve_traceable`` (JaxAllocation.exit_reason,
# threaded into RoundTelemetry.alloc_exit_reason by the training loops)
EXIT_CONVERGED = 0   # relative-objective criterion fired before the cap
EXIT_ITER_CAP = 1    # burned the full max_iters budget without converging
EXIT_NONFINITE = 2   # iterate went non-finite; froze on the last good point
EXIT_UNIFORM_FALLBACK = 3  # solver lost to the uniform default (safeguard)


class JaxAllocation(NamedTuple):
    alpha: jax.Array             # (..., K)
    beta: jax.Array              # (..., K)
    q: jax.Array                 # (..., K) sign-packet success probs
    p: jax.Array                 # (..., K) modulus-packet success probs
    objective: jax.Array         # (...,)
    iters: jax.Array             # (...,)  outer iterations actually used
    objectives: jax.Array        # (..., max_iters) per-outer-iter objective
                                 # trajectory (NaN beyond ``iters``)
    exit_reason: jax.Array       # (...,)  int32 EXIT_* code
    steps: jax.Array             # (...,)  int32 trips of the innermost
    #   serial loops (see ``solve_traceable``): the solve's work, where
    #   ``iters`` counts only its outer iterations


class _Caps(NamedTuple):
    """Dtype-bound numerical guards (see module docstring)."""
    exp_cap: float
    pow_cap: float
    h_floor: float
    log_floor: float
    newton_eps: float
    a_eps: float


def _caps(dtype) -> _Caps:
    if dtype == jnp.float64:
        return _Caps(AC.EXP_CAP, AC.POW_CAP, AC.H_FLOOR, AC.LOG_FLOOR,
                     1e-8, 1e-12)
    # f32: exp(80) ~ 5.5e34 and 2^120 ~ 1.3e36 stay finite; the H floor
    # saturates just inside -FLT_MAX.  a_eps must keep 1 - a_eps strictly
    # below 1.0 in f32 (1 - 1e-12 rounds to exactly 1.0, making om = 0
    # and NaN-ing the barrier gradient via 0 * inf); f32 spacing at 1.0
    # is ~6e-8, so 1e-6 is the boundary clip
    return _Caps(80.0, 120.0, -3e38, -85.0, 1e-4, 1e-6)


# ---------------------------------------------------------------------------
# problem constructors
# ---------------------------------------------------------------------------

def _default_dtype():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def problem_from_stats(g2, gb2, v, d2, gains, p_w, dim: int,
                       fl: FLConfig, dtype=None) -> JaxAllocationProblem:
    """Traceable constructor from per-client scalars (jnp arrays OK)."""
    dtype = dtype or _default_dtype()

    def cast(x):
        return jnp.asarray(x, dtype)

    A, B, C, D = AC.g_coefficients(jnp, cast(g2), cast(gb2), cast(v),
                                   cast(d2), fl.lipschitz_const,
                                   fl.learning_rate)
    return JaxAllocationProblem(
        A, B, C, D, cast(gains), cast(p_w),
        cast(float(dim)), cast(float(dim * fl.quant_bits + fl.b0_bits)),
        cast(fl.bandwidth_hz), cast(fl.noise_psd_w), cast(fl.latency_s),
        cast(fl.alpha_max))


def from_reference(prob: AllocationProblem, dtype=None,
                   pad_to: Optional[int] = None) -> JaxAllocationProblem:
    """Convert the NumPy reference problem into the pytree form.

    ``pad_to`` widens the client axis to that many entries by appending
    zero-coefficient pads (A=B=C=D=0, gains=p_w=1) and sets ``mask``.
    The pads contribute exactly ``+0.0`` to every masked ordered sum, so
    the real clients' solve is bit-identical to the unpadded problem.
    """
    dtype = dtype or _default_dtype()
    k = prob.n
    n_pad = 0 if pad_to is None else pad_to - k
    if n_pad < 0:
        raise ValueError(f'pad_to={pad_to} < K={k}')

    def cast(x):
        return jnp.asarray(np.asarray(x), dtype)

    def padded(x, fill):
        x = cast(x)
        if n_pad:
            x = jnp.concatenate([x, jnp.full((n_pad,), fill, dtype)])
        return x

    fl = prob.fl
    mask = None
    if pad_to is not None:
        mask = jnp.concatenate([jnp.ones((k,), dtype),
                                jnp.zeros((n_pad,), dtype)])
    return JaxAllocationProblem(
        padded(prob.coef.A, 0.0), padded(prob.coef.B, 0.0),
        padded(prob.coef.C, 0.0), padded(prob.coef.D, 0.0),
        padded(prob.gains, 1.0), padded(prob.p_w, 1.0),
        cast(prob.sign_bits), cast(prob.mod_bits),
        cast(fl.bandwidth_hz), cast(fl.noise_psd_w), cast(fl.latency_s),
        cast(fl.alpha_max), mask)


def stack_problems(probs: Sequence[AllocationProblem],
                   dtype=None) -> JaxAllocationProblem:
    """Stack reference problems into one batched pytree (every leaf gains
    a leading batch axis, so ``solve_batched`` maps ``in_axes=0``).

    Heterogeneous cohort sizes are allowed: every problem is zero-padded
    to the widest K (see ``from_reference(pad_to=...)``) and the stacked
    pytree carries a per-element ``mask`` — one ``solve_batched``
    dispatch then covers a ragged K sweep.  Homogeneous stacks keep
    ``mask=None`` (bit- and jit-cache-compatible with the old form)."""
    ks = {p.n for p in probs}
    pad_to = max(ks) if len(ks) > 1 else None
    js = [from_reference(p, dtype, pad_to=pad_to) for p in probs]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *js)


def batch_over_gains(prob: JaxAllocationProblem,
                     gains_b) -> JaxAllocationProblem:
    """Broadcast one problem over a (B, K) fading trajectory: one
    ``solve_batched`` dispatch then solves every draw."""
    gains_b = jnp.asarray(gains_b, prob.gains.dtype)
    b = gains_b.shape[0]

    def rep(x):
        return jnp.broadcast_to(x, (b,) + x.shape)

    return jax.tree.map(rep, prob)._replace(gains=gains_b)


# ---------------------------------------------------------------------------
# H terms / objective on the pytree
# ---------------------------------------------------------------------------

def _ordered_sum(x):
    """Strict left-to-right sum over the last axis.

    ``jnp.sum``'s reduction order is an XLA implementation detail that
    changes with the batch shape, so a vmapped solve would drift from a
    single solve by ulps that the iterative solver then amplifies.  An
    unrolled add chain pins the association (same idiom as the
    transport's ``_seq_client_mean``), making the engine's results
    invariant to batching — the basis of the bit-match guarantee in
    tests/test_allocation_jax.py.
    """
    acc = x[..., 0]
    for i in range(1, x.shape[-1]):
        acc = acc + x[..., i]
    return acc


def _msum(prob, x):
    """Client-axis ordered sum that ignores zero-pads.  With no mask the
    add chain is untouched; with one, pads multiply to exactly +0.0, so
    the real clients' partial sums keep their unpadded bit patterns."""
    return _ordered_sum(x if prob.mask is None else x * prob.mask)


def _bounded_fori(n, body, init, stop, early_exit, freezes=True):
    """``lax.fori_loop(0, n, body, init)`` with a convergence exit.

    ``stop(carry) -> bool[]`` reads the loop's own ``done`` flag; when
    ``early_exit`` the loop lowers to a bounded-trip ``lax.while_loop``
    (predicate ``i < n  &  ~stop``) that leaves at the iteration the
    flag fires.  Because every fixed-trip body freezes its carry once
    ``done`` is set, the two lowerings return bit-identical carries.
    Under vmap the batched ``while_loop`` keeps stepping until every
    element stops, select-freezing finished elements' carries — the
    masked all-converged predicate, for free.  The hard ``i < n`` bound
    keeps the loop compilable inside ``lax.scan`` (the fused f32
    in-round path).

    Returns ``(carry, trips)``: the trips taken before ``stop`` fired —
    the while form's own ``i``.  The fixed-trip form counts the trips
    it entered with ``stop`` still false (the later ones are frozen
    no-ops), so both lowerings report the same count.  A body that does
    not freeze (``freezes=False``: the golden-section and bisection
    searches, whose ``stop`` is only an exit tolerance) works on every
    fixed trip, which then counts as the static ``n`` and adds no op.
    """
    if not early_exit:
        if not freezes:
            return lax.fori_loop(0, n, body, init), n

        def counted(i, tc):
            trips, carry = tc
            return (trips + (~stop(carry)).astype(jnp.int32),
                    body(i, carry))

        trips, carry = lax.fori_loop(0, n, counted, (jnp.int32(0), init))
        return carry, trips

    def cond(ic):
        i, carry = ic
        return (i < n) & ~stop(carry)

    def wbody(ic):
        i, carry = ic
        return i + 1, body(i, carry)

    trips, carry = lax.while_loop(cond, wbody, (jnp.int32(0), init))
    return carry, trips


def _cs(prob):
    return (prob.A, prob.B, prob.C, prob.D)


def _h_s(prob, caps, beta):
    return AC.h_term(jnp, beta, prob.p_w, prob.gains, prob.sign_bits,
                     prob.bandwidth_hz, prob.noise_psd_w, prob.latency_s,
                     pow_cap=caps.pow_cap, h_floor=caps.h_floor)


def _h_v(prob, caps, beta):
    return AC.h_term(jnp, beta, prob.p_w, prob.gains, prob.mod_bits,
                     prob.bandwidth_hz, prob.noise_psd_w, prob.latency_s,
                     pow_cap=caps.pow_cap, h_floor=caps.h_floor)


def _h_s_prime(prob, caps, beta):
    return AC.h_term_prime(jnp, beta, prob.p_w, prob.gains, prob.sign_bits,
                           prob.bandwidth_hz, prob.noise_psd_w,
                           prob.latency_s, pow_cap=caps.pow_cap)


def _h_v_prime(prob, caps, beta):
    return AC.h_term_prime(jnp, beta, prob.p_w, prob.gains, prob.mod_bits,
                           prob.bandwidth_hz, prob.noise_psd_w,
                           prob.latency_s, pow_cap=caps.pow_cap)


def _objective(prob, caps, alpha, beta):
    return _msum(prob, AC.g_value(jnp, _cs(prob), alpha,
                                  _h_s(prob, caps, beta),
                                  _h_v(prob, caps, beta),
                                  exp_cap=caps.exp_cap))


def success_probs(prob: JaxAllocationProblem, alpha, beta):
    """(q, p) of eq. (11)/(13) on the pytree problem."""
    caps = _caps(prob.A.dtype)
    return AC.success_probs(jnp, alpha, _h_s(prob, caps, beta),
                            _h_v(prob, caps, beta),
                            log_floor=caps.log_floor)


# ---------------------------------------------------------------------------
# power split (Lemma 3): grid brackets + masked safeguarded Newton
# ---------------------------------------------------------------------------

def optimize_alpha(prob: JaxAllocationProblem, beta, n_grid: int = 256,
                   newton_iters: int = 40, caps: _Caps = None):
    caps = caps or _caps(prob.A.dtype)
    cs = _cs(prob)
    h_s, h_v = _h_s(prob, caps, beta), _h_v(prob, caps, beta)
    a_max = jnp.clip(prob.alpha_max, 1e-3, 1.0)
    # np.linspace semantics spelled out elementwise (start + i*step with
    # the endpoint pinned): jnp.linspace's traced-endpoint path rounds
    # differently under vmap, which the Newton polish then amplifies —
    # this form is bit-invariant to batching
    lo_a, hi_a = 1e-4, a_max - 1e-4
    step = (hi_a - lo_a) / (n_grid - 1)
    grid = lo_a + jnp.arange(n_grid, dtype=beta.dtype) * step
    grid = grid.at[-1].set(hi_a)                             # (n_grid,)

    # G' on the grid: (n_grid, K)
    gp = AC.g_prime_alpha(jnp, cs, grid[:, None], h_s[None, :],
                          h_v[None, :], exp_cap=caps.exp_cap,
                          a_eps=caps.a_eps)
    best_alpha = jnp.full_like(h_s, 1.0) * a_max
    best_val = AC.g_value(jnp, cs, best_alpha, h_s, h_v,
                          exp_cap=caps.exp_cap)

    # the reference collects sign-change brackets with np.nonzero; here
    # every interval runs the same safeguarded Newton, masked afterwards
    sign_change = jnp.signbit(gp[:-1]) != jnp.signbit(gp[1:])
    shape = sign_change.shape                                 # (n_grid-1, K)
    lo0 = jnp.broadcast_to(grid[:-1, None], shape)
    hi0 = jnp.broadcast_to(grid[1:, None], shape)
    flo = gp[:-1]
    eps = caps.newton_eps

    def body(_, carry):
        lo, hi, x = carry
        f = AC.g_prime_alpha(jnp, cs, x, h_s, h_v, exp_cap=caps.exp_cap,
                             a_eps=caps.a_eps)
        fp = (AC.g_prime_alpha(jnp, cs, x + eps, h_s, h_v,
                               exp_cap=caps.exp_cap,
                               a_eps=caps.a_eps) - f) / eps
        same = (flo < 0) == (f < 0)
        lo = jnp.where(same, x, lo)
        hi = jnp.where(same, hi, x)
        newton = x - f / fp
        mid = 0.5 * (lo + hi)
        good = jnp.isfinite(newton) & (newton > lo) & (newton < hi)
        return lo, hi, jnp.where(good, newton, mid)

    _, _, x = lax.fori_loop(0, newton_iters, body,
                            (lo0, hi0, 0.5 * (lo0 + hi0)))
    vals = AC.g_value(jnp, cs, x, h_s, h_v, exp_cap=caps.exp_cap)
    vals = jnp.where(sign_change & ~jnp.isnan(vals), vals, jnp.inf)
    j = jnp.argmin(vals, axis=0)                              # (K,)
    cand_val = jnp.take_along_axis(vals, j[None, :], axis=0)[0]
    cand_x = jnp.take_along_axis(x, j[None, :], axis=0)[0]
    return jnp.where(cand_val < best_val, cand_x, best_alpha)


# ---------------------------------------------------------------------------
# bandwidth via SCA / majorize-minimize + dual bisection
# ---------------------------------------------------------------------------

def _surrogate(prob, caps, alpha, beta0):
    a = jnp.clip(alpha, caps.a_eps, 1.0 - caps.a_eps)
    om = 1.0 - a
    hs0, hv0 = _h_s(prob, caps, beta0), _h_v(prob, caps, beta0)
    hs0p = _h_s_prime(prob, caps, beta0)
    hv0p = _h_v_prime(prob, caps, beta0)
    cs = _cs(prob)
    e0 = tuple(wv * hv0 / om - ws * hs0 / a for wv, ws in AC.TERM_W)

    def surrogate(beta):
        hs, hv = _h_s(prob, caps, beta), _h_v(prob, caps, beta)
        hs_lin = hs0 + hs0p * (beta - beta0)
        hv_lin = hv0 + hv0p * (beta - beta0)
        return AC.surrogate_value(jnp, cs, a, om, hs, hv, hs_lin, hv_lin,
                                  e0, exp_cap=caps.exp_cap)

    return surrogate


GOLDEN_ITERS = 48       # golden-section steps of one full search
BISECTION_ITERS = 60    # dual bisection steps on the sum constraint
BACKTRACK_ITERS = 27    # feasibility halvings of one barrier descent step


def _golden_vec(f, shape, dtype, iters: int = GOLDEN_ITERS,
                early_exit: bool = True, width_tol: float = 0.0):
    """Golden section on [BETA_MIN, BETA_MAX], elementwise.

    ``width_tol > 0`` stops once every element's bracket is narrower
    than it (tolerance-bounded exit: the returned midpoint is within
    ``width_tol/2`` of the fixed-trip one); ``width_tol=0`` runs the
    full fixed-trip schedule bit-identically (the interval never
    reaches exact zero width, so the predicate only trips the cap).
    Returns ``(midpoint, steps)``; ``steps`` is the static ``iters``
    unless the tolerance exit is on."""
    gr = (np.sqrt(5.0) - 1.0) / 2.0
    lo = jnp.full(shape, AC.BETA_MIN, dtype)
    hi = jnp.full(shape, AC.BETA_MAX, dtype)
    c = hi - gr * (hi - lo)
    d = lo + gr * (hi - lo)

    def body(_, carry):
        lo, hi, c, d, fc, fd = carry
        left = fc < fd
        hi = jnp.where(left, d, hi)
        lo = jnp.where(left, lo, c)
        c2 = hi - gr * (hi - lo)
        d2 = lo + gr * (hi - lo)
        return lo, hi, c2, d2, f(c2), f(d2)

    def stop(carry):
        return jnp.max(carry[1] - carry[0]) <= width_tol

    carry, steps = _bounded_fori(iters, body, (lo, hi, c, d, f(c), f(d)),
                                 stop, early_exit and width_tol > 0.0,
                                 freezes=False)
    return 0.5 * (carry[0] + carry[1]), steps


def optimize_beta_sca(prob: JaxAllocationProblem, alpha, beta0,
                      sca_rounds: int = 8, tol: float = 1e-6,
                      caps: _Caps = None, early_exit: bool = True,
                      inner_tol: float = 0.0):
    """-> ``(beta, steps)``: ``steps`` sums the golden-section steps of
    every search the SCA rounds ran — the free search, and when the sum
    constraint binds, one per dual bracket growth and bisection step
    plus the final one."""
    caps = caps or _caps(prob.A.dtype)
    dtype = beta0.dtype
    shape = beta0.shape
    # only the tolerance exit makes a search's step count vary; without
    # it the count is GOLDEN_ITERS per search, multiplied out once per
    # SCA round, so the grow and bisection bodies carry it unchanged
    tally = early_exit and inner_tol > 0.0

    def sca_body(_, carry):
        beta, prev, done, steps = carry
        surrogate = _surrogate(prob, caps, alpha, beta)

        def beta_of_lambda(lam):
            return _golden_vec(lambda b: surrogate(b) + lam * b, shape,
                               dtype, early_exit=early_exit,
                               width_tol=inner_tol)

        b0, steps0 = beta_of_lambda(jnp.asarray(0.0, dtype))

        def dual(_):
            # grow the dual upper bracket (×10 from 1.0; 30 steps reach
            # the reference's 1e30 stop); once `need` clears, further
            # trips are frozen no-ops — the while form exits there
            def grow(_, carry):
                hi, cont, n = carry
                b, t = beta_of_lambda(hi)
                need = cont & (_msum(prob, b) > 1.0) & (hi < 1e30)
                return (jnp.where(need, hi * 10.0, hi), need,
                        n + t if tally else n)

            (hi, _, n_grow), grows = _bounded_fori(
                30, grow, (jnp.asarray(1.0, dtype), jnp.asarray(True),
                           jnp.int32(0)),
                lambda c: ~c[1], early_exit)

            # ... then 60 bisection steps on the sum constraint
            # (``inner_tol`` stops once the dual bracket is relatively
            # that narrow — the fixed-trip schedule reaches 2^-60)
            def bis(_, lhn):
                lo, hi, n = lhn
                mid = 0.5 * (lo + hi)
                b, t = beta_of_lambda(mid)
                infeas = _msum(prob, b) > 1.0
                return (jnp.where(infeas, mid, lo),
                        jnp.where(infeas, hi, mid), n + t if tally else n)

            def bis_stop(lhn):
                return (lhn[1] - lhn[0]) <= inner_tol * lhn[1]

            (_, hi, n_bis), bisections = _bounded_fori(
                BISECTION_ITERS, bis,
                (jnp.asarray(0.0, dtype), hi, jnp.int32(0)), bis_stop,
                early_exit and inner_tol > 0.0, freezes=False)
            b, t = beta_of_lambda(hi)
            searched = (n_grow + n_bis + t if tally
                        else GOLDEN_ITERS * (grows + bisections + 1))
            return b * jnp.minimum(1.0, 1.0 / jnp.maximum(
                _msum(prob, b), 1e-12)), jnp.asarray(searched, jnp.int32)

        b, steps_dual = lax.cond(
            _msum(prob, b0) > 1.0, dual,
            lambda _: (b0, jnp.int32(0)), None)
        # MM guarantee: only accept descent on the true objective
        cur = _objective(prob, caps, alpha, b)
        accept = (cur <= prev) & ~done
        conv = jnp.abs(prev - cur) <= tol * (1.0 + jnp.abs(prev))
        beta2 = jnp.where(accept, b, beta)
        prev2 = jnp.where(done, prev, jnp.minimum(prev, cur))
        steps2 = jnp.where(done, steps, steps + steps0 + steps_dual)
        return beta2, prev2, done | conv, steps2

    prev0 = _objective(prob, caps, alpha, beta0)
    (beta, _, _, steps), _ = _bounded_fori(
        sca_rounds, sca_body,
        (beta0, prev0, jnp.asarray(False), jnp.int32(0)),
        lambda c: c[2], early_exit)
    return beta, steps


# ---------------------------------------------------------------------------
# low-complexity §IV-D: log-barrier + projected gradient descent
# ---------------------------------------------------------------------------

def optimize_beta_barrier(prob: JaxAllocationProblem, alpha, beta0,
                          mu0: float = 10.0, mu_growth: float = 10.0,
                          outer: int = 5, inner: int = 200,
                          lr: float = 1e-3, caps: _Caps = None,
                          early_exit: bool = True,
                          inner_tol: float = 0.0):
    """-> ``(beta, steps)``: ``steps`` counts the inner descent steps
    of every barrier level, each with its ``BACKTRACK_ITERS``
    feasibility halvings."""
    caps = caps or _caps(prob.A.dtype)
    dtype = beta0.dtype
    beta = jnp.maximum(beta0, 1e-4)
    s = _msum(prob, beta)
    beta = jnp.where(s >= 1.0, beta / s * 0.95, beta)
    ln10 = np.log(10.0)
    a = jnp.clip(alpha, caps.a_eps, 1.0 - caps.a_eps)
    om = 1.0 - a
    cs = _cs(prob)

    def gdbeta(b):
        return AC.g_dbeta(jnp, cs, a, om, _h_s(prob, caps, b),
                          _h_v(prob, caps, b), _h_s_prime(prob, caps, b),
                          _h_v_prime(prob, caps, b), exp_cap=caps.exp_cap)

    def outer_body(oi, carry):
        beta, steps = carry
        mu = jnp.asarray(mu0, dtype) * jnp.asarray(mu_growth, dtype) ** oi

        def inner_body(_, carry):
            beta, done = carry
            slack = 1.0 - _msum(prob, beta)
            grad = (gdbeta(beta)
                    - (1.0 / (mu * ln10))
                    * (1.0 / beta - 1.0 / (1.0 - beta) - 1.0 / slack))
            if prob.mask is not None:
                grad = grad * prob.mask   # pads hold their init point
            gn = jnp.sqrt(_ordered_sum(grad * grad))
            step = lr / (1.0 + gn)

            # feasibility backtracking: 27 halvings reach the reference's
            # t <= 1e-8 give-up threshold exactly
            def back(_, tc):
                t, new = tc
                infeas = (jnp.any(new <= 0) | jnp.any(new >= 1)
                          | (_msum(prob, new) >= 1.0))
                cont = infeas & (t > 1e-8)
                t2 = jnp.where(cont, 0.5 * t, t)
                new2 = jnp.where(cont, beta - t2 * step * grad, new)
                return t2, new2

            t, new = lax.fori_loop(0, BACKTRACK_ITERS, back,
                                   (jnp.asarray(1.0, dtype),
                                    beta - step * grad))
            give_up = (gn < 1e-14) | (t <= 1e-8)
            # displacement criterion for the ~28k-step descent: once the
            # backtracked move falls below ``inner_tol`` the iterate has
            # stalled at this mu — tolerance-bounded (the fixed-trip form
            # keeps inching; bound documented in core/README.md).
            # inner_tol=0 only stops on an exactly-fixed point, which is
            # absorbing and therefore bit-identical.
            stalled = jnp.max(jnp.abs(new - beta)) <= inner_tol
            beta2 = jnp.where(~done & ~give_up, new, beta)
            return beta2, done | give_up | stalled

        (beta, _), descents = _bounded_fori(inner, inner_body,
                                            (beta, jnp.asarray(False)),
                                            lambda c: c[1], early_exit)
        return beta, steps + descents * (1 + BACKTRACK_ITERS)

    return lax.fori_loop(0, outer, outer_body, (beta, jnp.int32(0)))


# ---------------------------------------------------------------------------
# Algorithm 1: alternating optimization
# ---------------------------------------------------------------------------

def solve_traceable(prob: JaxAllocationProblem, method: str = 'alternating',
                    max_iters: int = 6, tol: float = 1e-5,
                    n_grid: int = 256, newton_iters: int = 40,
                    early_exit: bool = True,
                    inner_tol: float = 0.0) -> JaxAllocation:
    """The solver as a pure traceable function — embed in any jit/vmap.

    ``early_exit`` lowers every convergence-flagged loop (the outer
    alternating loop, the SCA rounds, the dual bracket growth, the
    barrier descent) to a bounded-trip ``lax.while_loop`` that leaves
    when its ``done`` flag fires — bit-identical to the fixed-trip
    lowering, vmap-safe, scan-compilable.  ``inner_tol > 0``
    additionally unlocks tolerance-bounded exits of the golden-section /
    dual-bisection / barrier inner loops (see core/README.md for the
    accuracy contract); 0 keeps those loops reference-faithful.

    ``steps`` counts the trips of the innermost serial loops of the
    iterations that ran: for ``alternating`` every golden-section step
    of every search, for ``barrier`` every descent step with its
    backtracking halvings.  Alpha's fixed Newton polish is left out;
    ``uniform`` counts 0.  At ``inner_tol=0`` the count is the same under
    either lowering; with a tolerance exit the fixed-trip form of
    ``alternating`` counts every search's and bisection's full schedule.
    """
    caps = _caps(prob.A.dtype)
    dtype = prob.A.dtype
    k = prob.gains.shape[-1]
    if prob.mask is None:
        beta_u = jnp.full((k,), 1.0 / k, dtype)
    else:
        beta_u = prob.mask / _ordered_sum(prob.mask)
    alpha_u = jnp.full((k,), 0.5, dtype)
    nan_objs = jnp.full((max_iters,), jnp.nan, dtype)
    if method == 'uniform':
        q, p = success_probs(prob, alpha_u, beta_u)
        return JaxAllocation(alpha_u, beta_u, q, p,
                             _objective(prob, caps, alpha_u, beta_u),
                             jnp.int32(0), nan_objs,
                             jnp.int32(EXIT_CONVERGED), jnp.int32(0))

    uniform_obj = _objective(prob, caps, alpha_u, beta_u)
    use_barrier = method == 'barrier'

    def body(i, carry):
        alpha, beta, prev, done, bad_seen, iters, objs, steps = carry
        alpha_n = optimize_alpha(prob, beta, n_grid, newton_iters, caps)
        if use_barrier:
            beta_n, steps_n = optimize_beta_barrier(
                prob, alpha_n, beta, caps=caps, early_exit=early_exit,
                inner_tol=inner_tol)
        else:
            beta_n, steps_n = optimize_beta_sca(prob, alpha_n, beta,
                                                caps=caps,
                                                early_exit=early_exit,
                                                inner_tol=inner_tol)
        obj = _objective(prob, caps, alpha_n, beta_n)
        # a non-finite iterate (f32 saturation) must not poison the
        # carry: freeze on the last good point instead of accepting it
        bad = ~jnp.isfinite(obj)
        conv = jnp.abs(prev - obj) <= tol * (1.0 + jnp.abs(obj))
        keep = done | bad
        alpha2 = jnp.where(keep, alpha, alpha_n)
        beta2 = jnp.where(keep, beta, beta_n)
        prev2 = jnp.where(keep, prev, obj)
        iters2 = jnp.where(keep, iters, i + 1)
        objs2 = objs.at[i].set(jnp.where(keep, jnp.nan, obj))
        steps2 = jnp.where(done, steps, steps + steps_n)
        return (alpha2, beta2, prev2, done | conv | bad,
                bad_seen | (bad & ~done), iters2, objs2, steps2)

    init = (alpha_u, beta_u, jnp.asarray(jnp.inf, dtype),
            jnp.asarray(False), jnp.asarray(False), jnp.int32(0),
            nan_objs, jnp.int32(0))
    (alpha, beta, prev, done, bad_seen, iters, objs, steps), _ = (
        _bounded_fori(max_iters, body, init, lambda c: c[3], early_exit))
    # safeguard: never return anything worse than the uniform default.
    # Written NaN-proof (~(prev <= uniform)) so a non-finite objective
    # falls back to uniform instead of escaping the comparison
    worse = ~(prev <= uniform_obj)
    alpha = jnp.where(worse, alpha_u, alpha)
    beta = jnp.where(worse, beta_u, beta)
    prev = jnp.where(worse, uniform_obj, prev)
    reason = jnp.where(
        worse, jnp.int32(EXIT_UNIFORM_FALLBACK),
        jnp.where(bad_seen, jnp.int32(EXIT_NONFINITE),
                  jnp.where(done, jnp.int32(EXIT_CONVERGED),
                            jnp.int32(EXIT_ITER_CAP))))
    q, p = success_probs(prob, alpha, beta)
    return JaxAllocation(alpha, beta, q, p, prev, iters, objs, reason,
                         steps)


_STATIC = ('method', 'max_iters', 'tol', 'n_grid', 'newton_iters',
           'early_exit', 'inner_tol')

_solve_jit = jax.jit(solve_traceable, static_argnames=_STATIC)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _solve_batched_jit(prob, method='alternating', max_iters=6, tol=1e-5,
                       n_grid=256, newton_iters=40, early_exit=True,
                       inner_tol=0.0):
    return jax.vmap(lambda pr: solve_traceable(
        pr, method, max_iters, tol, n_grid, newton_iters, early_exit,
        inner_tol))(prob)


def solve_batched(prob: JaxAllocationProblem, method: str = 'alternating',
                  max_iters: int = 6, tol: float = 1e-5, n_grid: int = 256,
                  newton_iters: int = 40, early_exit: bool = True,
                  inner_tol: float = 0.0) -> JaxAllocation:
    """One dispatch over a batch of problems.

    Every leaf of ``prob`` must carry a leading batch axis (see
    ``stack_problems`` / ``batch_over_gains``).  Runs under x64 so the
    batched solutions carry full f64 precision (and keep the jit cache
    keyed consistently — the wrapper re-enters the same trace context on
    every call).  Early exit composes with the batch: the lowered
    ``while_loop`` steps until every element converged, select-freezing
    finished elements — still bit-identical to a loop of single solves.
    """
    with jax.enable_x64():
        return _solve_batched_jit(prob, method, max_iters, tol, n_grid,
                                  newton_iters, early_exit, inner_tol)


@functools.partial(jax.jit, static_argnames=('dim', 'fl', 'method',
                                             'max_iters', 'tol',
                                             'early_exit'))
def _solve_stats_jit(g2, gb2, v, d2, gains, p_w, dim, fl, method,
                     max_iters, tol, early_exit):
    prob = problem_from_stats(g2, gb2, v, d2, gains, p_w, dim, fl,
                              dtype=jnp.float64)
    return solve_traceable(prob, method, max_iters, tol=tol,
                           early_exit=early_exit)


def solve_from_stats(g2, gb2, v, d2, gains, p_w, dim: int, fl: FLConfig,
                     method: str = 'alternating', max_iters: int = 6,
                     tol: float = 1e-5,
                     early_exit: bool = True) -> JaxAllocation:
    """One jitted dispatch from the devices' scalar report to the round's
    allocation — the ``allocation_backend='jax'`` path of the training
    drivers (no host NumPy between the stats and (q, p))."""
    with jax.enable_x64():
        return _solve_stats_jit(g2, gb2, v, d2, gains, p_w, dim, fl,
                                method, max_iters, tol, early_exit)


def solve(prob, method: str = 'alternating', max_iters: int = 6,
          tol: float = 1e-5, early_exit: bool = True,
          inner_tol: float = 0.0) -> Allocation:
    """Drop-in for ``allocation.solve``: accepts the NumPy reference
    problem (or a pre-built pytree), solves on-device under x64, returns
    the host :class:`Allocation` with ``info['iters_used']`` /
    ``info['exit_reason']`` / ``info['steps']`` reporting the solver
    effort."""
    with jax.enable_x64():
        jp = from_reference(prob) if isinstance(prob, AllocationProblem) \
            else prob
        sol = _solve_jit(jp, method=method, max_iters=max_iters, tol=tol,
                         early_exit=early_exit, inner_tol=inner_tol)
        objs = np.asarray(sol.objectives)
    iters_used = int(sol.iters)
    return Allocation(np.asarray(sol.alpha, np.float64),
                      np.asarray(sol.beta, np.float64),
                      np.asarray(sol.q, np.float64),
                      np.asarray(sol.p, np.float64),
                      float(sol.objective),
                      {'iters': iters_used, 'iters_used': iters_used,
                       'exit_reason': int(sol.exit_reason),
                       'steps': int(sol.steps),
                       'method': method, 'backend': 'jax',
                       'objectives': [float(o) for o in
                                      objs[~np.isnan(objs)]]})
