"""Float64 NumPy solver of the eq. (28) bandwidth/power allocation.

A frozen copy of the program's host-side reference engine (alternating
optimisation: Lemma 3 Newton-Raphson for the power split, SCA with dual
bisection and golden-section search for the bandwidth), kept here so the
yardstick cannot move with the code under test.  Only the 'uniform' and
'alternating' methods are kept.
"""
from __future__ import annotations

import numpy as np

EXP_CAP = 600.0
POW_CAP = 500.0
H_FLOOR = -1e150
BETA_MIN = 1e-6
BETA_MAX = 1.0 - 1e-9
LOG_FLOOR = -745.0
TERM_W = ((1.0, 0.0), (2.0, 0.0), (1.0, 1.0), (0.0, 1.0))
_INF = float('inf')


def _h(beta, p_w, gain, n_bits, bw, n0, tau):
    bb = beta * bw
    expo = np.minimum(2.0 * n_bits / (bb * tau), POW_CAP)
    return np.maximum((bb * n0 / (4.0 * p_w * gain)) * (1.0 - 2.0 ** expo),
                      H_FLOOR)


def _h_prime(beta, p_w, gain, n_bits, bw, n0, tau):
    c1 = bw * n0 / (4.0 * p_w * gain)
    expo = np.minimum(2.0 * n_bits / (beta * bw * tau), POW_CAP)
    pow2 = 2.0 ** expo
    return c1 * ((1.0 - pow2) + pow2 * np.log(2.0) * expo)


def _exponents(alpha, h_s, h_v):
    a = np.clip(alpha, 1e-12, 1.0)
    om = np.clip(1.0 - alpha, 1e-12, 1.0)
    t1 = np.where(alpha >= 1.0, -_INF, h_v / om)
    t4 = np.where(alpha <= 0.0, _INF, -h_s / a)
    return t1, 2.0 * t1, t1 + t4, t4


def _g(cs, alpha, h_s, h_v):
    t = _exponents(alpha, h_s, h_v)
    return sum(c * np.exp(np.minimum(e, EXP_CAP)) for c, e in zip(cs, t))


def _g_prime(cs, alpha, h_s, h_v):
    a = np.clip(alpha, 1e-12, 1.0 - 1e-12)
    om = 1.0 - a
    t1, t2, t3, t4 = _exponents(a, h_s, h_v)
    dv = h_v / om ** 2
    ds = h_s / a ** 2
    return (cs[0] * np.exp(np.minimum(t1, EXP_CAP)) * dv
            + cs[1] * np.exp(np.minimum(t2, EXP_CAP)) * 2.0 * dv
            + cs[2] * np.exp(np.minimum(t3, EXP_CAP)) * (dv + ds)
            + cs[3] * np.exp(np.minimum(t4, EXP_CAP)) * ds)


class Problem:
    """Per-client eq. (27) coefficients plus the channel of one round."""

    def __init__(self, g2, gb2, v, d2, gains, p_w, dim, fl):
        g2, gb2, v, d2 = (np.asarray(x, np.float64) for x in (g2, gb2, v, d2))
        le = fl['lipschitz'] * fl['learning_rate']
        self.cs = (2.0 * (-2.0 * g2 - gb2 + 3.0 * v),
                   g2 + gb2 - 2.0 * v,
                   le * (g2 - gb2 + d2),
                   le * gb2 + np.zeros_like(g2))
        self.gains = np.asarray(gains, np.float64)
        self.p_w = np.asarray(p_w, np.float64)
        self.k = len(self.gains)
        self.sign_bits = float(dim)
        self.mod_bits = float(dim * fl['quant_bits'] + fl['b0_bits'])
        self.chan = (fl['bandwidth_hz'], fl['noise_psd_w'], fl['latency_s'])
        self.alpha_max = fl.get('alpha_max', 1.0)

    def h_s(self, beta):
        return _h(beta, self.p_w, self.gains, self.sign_bits, *self.chan)

    def h_v(self, beta):
        return _h(beta, self.p_w, self.gains, self.mod_bits, *self.chan)

    def h_s_prime(self, beta):
        return _h_prime(beta, self.p_w, self.gains, self.sign_bits,
                        *self.chan)

    def h_v_prime(self, beta):
        return _h_prime(beta, self.p_w, self.gains, self.mod_bits,
                        *self.chan)

    def objective(self, alpha, beta):
        return float(np.sum(_g(self.cs, alpha, self.h_s(beta),
                               self.h_v(beta))))

    def objective_terms(self, q, p):
        """The objective's terms in the success probabilities: with
        q = exp(h_s / alpha) and p = exp(h_v / (1 - alpha)) the four terms
        of `_g` are p, p^2, p/q and 1/q.  -> (4, K)."""
        q, p = np.asarray(q, np.float64), np.asarray(p, np.float64)
        with np.errstate(divide='ignore'):
            inv_q = np.minimum(1.0 / q, np.exp(EXP_CAP))
        return np.stack([self.cs[0] * p, self.cs[1] * p * p,
                         self.cs[2] * p * inv_q, self.cs[3] * inv_q])

    def objective_qp(self, q, p):
        return float(np.sum(self.objective_terms(q, p)))

    def success(self, alpha, beta):
        hs, hv = self.h_s(beta), self.h_v(beta)
        q = np.where(alpha > 0, np.exp(np.maximum(
            hs / np.clip(alpha, 1e-12, 1.0), LOG_FLOOR)), 0.0)
        p = np.where(alpha < 1, np.exp(np.maximum(
            hv / np.clip(1.0 - alpha, 1e-12, 1.0), LOG_FLOOR)), 0.0)
        return q, p


def _optimize_alpha(prob, beta, n_grid=256, newton_iters=40):
    hs, hv = prob.h_s(beta), prob.h_v(beta)
    k = prob.k
    a_max = min(max(prob.alpha_max, 1e-3), 1.0)
    grid = np.linspace(1e-4, a_max - 1e-4, n_grid)
    gp = np.stack([_g_prime(prob.cs, np.full(k, a), hs, hv) for a in grid])
    best_alpha = np.full(k, a_max)
    best_val = _g(prob.cs, best_alpha, hs, hv)
    change = np.signbit(gp[:-1]) != np.signbit(gp[1:])
    idx_i, idx_k = np.nonzero(change)
    if idx_k.size:
        lo, hi = grid[idx_i].copy(), grid[idx_i + 1].copy()
        cs = tuple(c[idx_k] for c in prob.cs)
        hs_b, hv_b = hs[idx_k], hv[idx_k]
        flo = gp[idx_i, idx_k]
        x = 0.5 * (lo + hi)
        eps = 1e-8
        for _ in range(newton_iters):
            f = _g_prime(cs, x, hs_b, hv_b)
            fp = (_g_prime(cs, x + eps, hs_b, hv_b) - f) / eps
            same = (flo < 0) == (f < 0)
            lo = np.where(same, x, lo)
            hi = np.where(same, hi, x)
            with np.errstate(divide='ignore', invalid='ignore'):
                newton = x - f / fp
            good = np.isfinite(newton) & (newton > lo) & (newton < hi)
            x = np.where(good, newton, 0.5 * (lo + hi))
        vals = _g(cs, x, hs_b, hv_b)
        for j in range(idx_k.size):
            if vals[j] < best_val[idx_k[j]]:
                best_val[idx_k[j]] = vals[j]
                best_alpha[idx_k[j]] = x[j]
    return best_alpha


def _surrogate(prob, alpha, beta0):
    a = np.clip(alpha, 1e-12, 1 - 1e-12)
    om = 1.0 - a
    hs0, hv0 = prob.h_s(beta0), prob.h_v(beta0)
    hs0p, hv0p = prob.h_s_prime(beta0), prob.h_v_prime(beta0)
    e0 = [wv * hv0 / om - ws * hs0 / a for wv, ws in TERM_W]

    def value(beta):
        hs, hv = prob.h_s(beta), prob.h_v(beta)
        hs_lin = hs0 + hs0p * (beta - beta0)
        hv_lin = hv0 + hv0p * (beta - beta0)
        total = np.zeros_like(hs)
        for j, (wv, ws) in enumerate(TERM_W):
            c = prob.cs[j]
            t_pos = c * np.exp(np.minimum(wv * hv_lin / om - ws * hs / a,
                                          EXP_CAP))
            e = wv * hv / om - ws * hs_lin / a
            t_neg = c * np.exp(np.minimum(e0[j], EXP_CAP)) * (1.0 + e - e0[j])
            total = total + np.where(c >= 0, t_pos, t_neg)
        return total
    return value


def _golden(f, lo, hi, shape, iters=48):
    """Golden-section minimisation of an elementwise-convex f over
    [lo, hi], vectorised over every element of ``shape``."""
    gr = (np.sqrt(5.0) - 1.0) / 2.0
    lo, hi = np.full(shape, lo), np.full(shape, hi)
    c, d = hi - gr * (hi - lo), lo + gr * (hi - lo)
    fc, fd = f(c), f(d)
    for _ in range(iters):
        left = fc < fd
        hi = np.where(left, d, hi)
        lo = np.where(left, lo, c)
        c, d = hi - gr * (hi - lo), lo + gr * (hi - lo)
        fc, fd = f(c), f(d)
    return 0.5 * (lo + hi)


def _optimize_beta(prob, alpha, beta0, sca_rounds=8, tol=1e-6):
    """SCA rounds; each solves the separable surrogate under sum(beta) <= 1
    by a dual search on the multiplier.  The search cuts its bracket 64
    ways per step (ten steps: the width sixty halvings leave), evaluating
    the 63 inner multipliers in one vectorised golden-section call."""
    k = prob.k
    beta = beta0.copy()
    prev = prob.objective(alpha, beta)
    for _ in range(sca_rounds):
        sur = _surrogate(prob, alpha, beta)

        def betas_of(lams):
            lam = np.asarray(lams, np.float64)[:, None]
            return _golden(lambda b: sur(b) + lam * b, BETA_MIN, BETA_MAX,
                           (lam.shape[0], k))
        b = betas_of([0.0])[0]
        if b.sum() > 1.0:
            lo, hi = 0.0, 1.0
            while betas_of([hi])[0].sum() > 1.0 and hi < 1e30:
                hi *= 10.0
            for _ in range(10):
                grid = lo + (hi - lo) * np.arange(1, 64) / 64.0
                over = betas_of(grid).sum(axis=1) > 1.0
                if over.all():
                    lo = grid[-1]
                else:
                    j = int(np.argmin(over))
                    hi = grid[j]
                    lo = grid[j - 1] if j > 0 else lo
            b = betas_of([hi])[0]
            b *= min(1.0, 1.0 / max(b.sum(), 1e-12))
        cur = prob.objective(alpha, b)
        if cur <= prev:
            beta = b
        if abs(prev - cur) <= tol * (1.0 + abs(prev)):
            break
        prev = min(prev, cur)
    return beta


def solve(prob, method='alternating', max_iters=6, tol=1e-5):
    """-> (q, p, alpha, beta, outer iterations) in float64."""
    k = prob.k
    beta = np.full(k, 1.0 / k)
    alpha = np.full(k, 0.5)
    if method == 'uniform':
        q, p = prob.success(alpha, beta)
        return q, p, alpha, beta, 0
    if method != 'alternating':
        raise ValueError(method)
    uniform_obj = prob.objective(alpha, beta)
    prev = np.inf
    iters = 0
    for it in range(max_iters):
        iters = it + 1
        alpha = _optimize_alpha(prob, beta)
        beta = _optimize_beta(prob, alpha, beta)
        obj = prob.objective(alpha, beta)
        done = abs(prev - obj) <= tol * (1.0 + abs(obj))
        prev = obj
        if done:
            break
    if prev > uniform_obj:
        alpha, beta = np.full(k, 0.5), np.full(k, 1.0 / k)
    q, p = prob.success(alpha, beta)
    return q, p, alpha, beta, iters
