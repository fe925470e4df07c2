"""Paper-scale wireless FL simulator — Algorithm 2, end to end.

One object runs the full SP-FL pipeline on the paper's CNN/CIFAR setting
(K devices, Rayleigh uplink, hierarchical allocation) and every §V
baseline, producing the histories all Figs. 2–10 benchmarks plot.

Per round n (Algorithm 2):
  1. broadcast w_n (free; downlink assumed error-free, §II-C)
  2. each device computes g_{k,n} = ∇F_k(w_n)           (vmapped, jitted)
  3. devices report ||g_{k,n}|| (+ δ_k scalars)           (error-free, §IV)
  4. PS solves eq. (28) -> (alpha_n, beta_n) -> (q, p)
  5. uplink transmission simulated by the chosen transport (jitted)
  6. PS aggregates (eq. (17)) and updates w (eq. (18))

Step 4 runs on the engine picked by ``FLConfig.allocation_backend``:
'numpy' is the host-side float64 reference (a jit barrier + host sync
per round, so the alternating method is capped at 2 outer iterations),
'jax' is the jitted on-device port (``repro.core.allocation_jax`` —
stats, eq. (28) solve and (q, p) in one dispatch, no host round-trip,
6 outer iterations by default).  ``FLConfig.allocation_cadence=
'per_round'`` additionally evolves the channel gains every round via
the seeded block-fading process (``channel.block_fading_trajectory``)
instead of freezing the round-0 geometry.

``FLConfig.round_fusion`` selects how rounds are dispatched:

* ``'none'`` (default) — the host loop above: one jitted dispatch per
  stage, telemetry ring-pushed per round, flushed on cadence.
* ``'eager'`` — the ENTIRE round (grads -> fading step -> f32 eq. (28)
  solve -> transport -> update -> compensation -> telemetry push) is one
  jitted body, dispatched once per round from a host loop.
* ``'scan'`` — the same body rolled over a whole telemetry segment by
  ``jax.lax.scan``: ONE dispatch per ``scan_segment_rounds`` rounds
  (default: ``telemetry_flush_every``), with params, compensation state,
  PRNG key, AR(1) shadowing state and the telemetry ring as scan carry.
  Zero device->host transfers happen between segment boundaries; the
  boundary does one ring flush (one ``device_get``) plus the eval.

'eager' and 'scan' trace the SAME round body, so they agree bit-exactly
on every integer field and to f32 rounding on floats — the parity the
fused-round tests pin.  Fused modes solve eq. (28) in float32 *inside*
the trace (``allocation_jax`` f32 caps; see core/README.md for the
measured f32-vs-f64 contract) and therefore require
``allocation_backend='jax'`` on allocating transports.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro import adversary
from repro import population as pop
from repro.configs.base import FLConfig
from repro.core import allocation as alloc
from repro.core import allocation_jax as alloc_jax
from repro.core import channel, convergence, transport
from repro.core import quantize as quantize_mod
from repro.models.cnn import cnn_accuracy, cnn_forward, cnn_loss, init_cnn
from repro.obs import record as obs_record
from repro.obs import ringbuf as obs_ring
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.sink import JsonlSink, run_manifest
from repro.obs.trace import stage_scope


@dataclass
class FLHistory:
    loss: List[float] = field(default_factory=list)
    test_acc: List[float] = field(default_factory=list)
    bound: List[float] = field(default_factory=list)          # per-round RHS
    loss_delta: List[float] = field(default_factory=list)     # measured drop
    payload_bits: List[float] = field(default_factory=list)
    sign_ok_frac: List[float] = field(default_factory=list)
    mod_ok_frac: List[float] = field(default_factory=list)
    q_mean: List[float] = field(default_factory=list)         # mean sign succ
    p_mean: List[float] = field(default_factory=list)         # mean mod succ
    sign_agreement: List[float] = field(default_factory=list)  # packed wire
    alloc_iters: List[float] = field(default_factory=list)     # solver outer
    # iterations to converge (NaN on rounds/paths without a solve)
    alloc_steps: List[float] = field(default_factory=list)     # solver inner
    # loop trips (RoundTelemetry.alloc_steps; NaN likewise)
    alloc_dual_rounds: List[float] = field(default_factory=list)  # dual
    # bisection tree rounds (RoundTelemetry.alloc_dual_rounds; NaN likewise)
    alloc_exit_reason: List[float] = field(default_factory=list)  # EXIT_*
    retransmissions: List[float] = field(default_factory=list)
    # adversarial-cohort telemetry (populated when the knobs are on):
    # fraction of clients active (not straggling/dropped) and fraction
    # screened out by the packed-domain byzantine defense
    participation_frac: List[float] = field(default_factory=list)
    suspect_frac: List[float] = field(default_factory=list)
    # host wall-time of step 4.  On allocation_backend='numpy' this is
    # the full eq. (28) solve; on 'jax' the solve is an async device
    # dispatch, so this records only the (intentionally tiny) host cost
    # of issuing it — the solve itself overlaps the transport step.
    alloc_time_s: List[float] = field(default_factory=list)
    round_time_s: List[float] = field(default_factory=list)

    def as_dict(self) -> Dict[str, List[float]]:
        return dataclasses.asdict(self)


class FLSimulator:
    """K-device wireless FL over the paper's CNN."""

    def __init__(self, fl: FLConfig, client_x: np.ndarray,
                 client_y: np.ndarray, test_x: np.ndarray,
                 test_y: np.ndarray, seed: Optional[int] = None):
        self.fl = fl
        self._population = fl.population_n > 0
        seed = fl.seed if seed is None else seed
        self._seed = seed
        if self._population:
            # population regime: client_x holds the S materialized data
            # SHARDS of the virtual device -> shard mapping, and K is
            # the per-round cohort width — everything per-device is
            # lazily materialized from (seed, device id) by
            # repro.population, so per-round cost is O(K), never O(N)
            self.K = pop.cohort_size(fl)
            if self.K > fl.population_n:
                raise ValueError(f'cohort_size {self.K} > population_n '
                                 f'{fl.population_n}')
            if fl.cohort_sampler not in pop.COHORT_SAMPLERS:
                raise ValueError(f'cohort_sampler must be one of '
                                 f'{pop.COHORT_SAMPLERS}, got '
                                 f'{fl.cohort_sampler!r}')
            if fl.transport not in ('spfl', 'spfl_retx', 'error_free'):
                raise ValueError(
                    'population mode is defined for the spfl/spfl_retx/'
                    'error_free transports (the analytic baselines pin '
                    f'static geometry), got {fl.transport!r}')
            if (fl.cohort_sampler == 'availability'
                    and fl.transport == 'error_free'):
                raise ValueError(
                    "cohort_sampler='availability' produces ragged "
                    'cohorts, which ride the spfl zero-weight padding — '
                    'the error_free transport has no active mask')
            if (fl.transport in ('spfl', 'spfl_retx')
                    and fl.allocation_backend != 'jax'):
                raise ValueError(
                    "population mode requires allocation_backend='jax' "
                    'on allocating transports — eq. (28) must re-solve '
                    'per sampled cohort on-device')
            if fl.compensation == 'last_local':
                raise ValueError(
                    "compensation='last_local' is undefined under "
                    'partial participation: cohort slots have no stable '
                    'device identity across rounds')
            if fl.attack == 'labelflip':
                raise ValueError(
                    "attack='labelflip' is undefined in population mode:"
                    ' data shards are shared across virtual devices, so '
                    'poisoning a shard is not poisoning a device')
            self._pop_key = pop.population_key(seed)
        else:
            self.K = client_x.shape[0]
            assert self.K == fl.n_devices, (self.K, fl.n_devices)
            self._pop_key = None
        self.key = jax.random.PRNGKey(seed)
        # host-side eq. (28) solves performed (stays 0 on the jax
        # backend — the per-round no-host-solve guarantee tests assert on)
        self.host_solver_calls = 0
        self.params = init_cnn(jax.random.fold_in(self.key, 0))
        flat, self.unravel = ravel_pytree(self.params)
        self.dim = flat.shape[0]
        self.client_x = jnp.asarray(client_x)
        self.client_y = jnp.asarray(client_y)
        # adversarial cohort: membership fixed once per run by a seeded
        # permutation; label-flip poisons the byzantine rows' data HERE,
        # at setup — that attacker's radio stays honest
        # population mode draws byzantine membership per-id per cohort
        # instead (population.byzantine_ids — lazily, from device id)
        self.byz_mask = (adversary.byzantine_mask(seed, self.K,
                                                  fl.attack_frac)
                         if fl.attack != 'none' and not self._population
                         else None)
        if fl.attack == 'labelflip':
            n_classes = int(np.max(np.asarray(client_y))) + 1
            self.client_y = adversary.flip_labels(self.client_y,
                                                  self.byz_mask, n_classes)
        # straggler chain state (True = active), stepped once per round
        # by the non-fused loop; the fused modes carry it in the scan
        self._straggler = adversary.straggler_init(self.K)
        self.test_x = jnp.asarray(test_x)
        self.test_y = jnp.asarray(test_y)
        if self._population:
            # geometry/power are lazily materialized per cohort from the
            # population key; these placeholders only size the closures
            # of the unused static-geometry baselines
            self.gains = np.ones(self.K)
            self.p_w = np.full(self.K, fl.tx_power_w)
        else:
            # static wireless geometry (paper: uniform in a 500 m cell)
            dist = channel.sample_distances(
                jax.random.fold_in(self.key, 1), self.K, fl.cell_radius_m)
            self.gains = channel.path_gain(np.asarray(dist),
                                           fl.path_loss_exp)
            self.p_w = np.full(self.K, fl.tx_power_w)
        # compensation state (flat modulus vector or per-client stack)
        if fl.compensation == 'last_local':
            self.gbar = jnp.zeros((self.K, self.dim))
        else:
            self.gbar = jnp.zeros((self.dim,))
        self._round = 0
        # host spans (the process-wide record: fused_segment, the
        # telemetry flush, alloc_solve / update of the host loop); the
        # stages inside the jitted round are obs/ scopes (obs.trace.STAGES)
        self.trace = obs_trace.SPANS
        # host metrics channels, fed from flushed telemetry rows
        self.metrics = MetricsRegistry()
        self._build_jits()

    # ------------------------------------------------------------------
    def _build_jits(self):
        unravel = self.unravel

        @jax.jit
        def per_client_grads(params, xs, ys):
            def one(x, y):
                loss, g = jax.value_and_grad(cnn_loss)(params, x, y)
                flat, _ = ravel_pytree(g)
                return loss, flat
            losses, grads = jax.vmap(one, in_axes=(0, 0))(xs, ys)
            return losses, grads            # (K,), (K, l)

        @jax.jit
        def global_metrics(params, xs, ys, tx, ty):
            loss = jnp.mean(jax.vmap(
                lambda x, y: cnn_loss(params, x, y))(xs, ys))
            acc = cnn_accuracy(params, tx, ty)
            return loss, acc

        @jax.jit
        def apply_update(params, ghat_flat):
            g = unravel(ghat_flat)
            return jax.tree.map(
                lambda p, gg: p - self.fl.learning_rate * gg, params, g)

        self._per_client_grads = per_client_grads
        self._global_metrics = global_metrics
        self._apply_update = apply_update

        fl = self.fl
        gains = jnp.asarray(self.gains)
        p_w = jnp.asarray(self.p_w)
        beta_uniform = jnp.full((self.K,), 1.0 / self.K)

        byz_mask = self.byz_mask

        @functools.partial(jax.jit, static_argnames=('kind',))
        def run_transport(kind, grads, gbar, q, p, key, round_idx,
                          active=None, byz=None):
            if kind in ('spfl', 'spfl_retx'):
                # population mode passes the cohort's per-id byzantine
                # membership in ``byz``; the legacy regime closes over
                # the run-static slot mask
                return transport.spfl_aggregate(
                    grads, gbar, q, p, fl.quant_bits, fl.b0_bits, key,
                    n_retx=1 if kind == 'spfl_retx' else 0, wire=fl.wire,
                    round_idx=round_idx, channel=fl.channel,
                    attack=fl.attack,
                    byz_mask=byz_mask if byz is None else byz,
                    attack_scale=fl.attack_scale, active=active,
                    screen=fl.screen, screen_z=fl.screen_z,
                    min_participation=fl.min_participation)
            if kind == 'dds':
                return transport.dds_aggregate(
                    grads, beta_uniform, gains, p_w, fl, key)
            if kind == 'onebit':
                return transport.onebit_aggregate(
                    grads, beta_uniform, gains, p_w, fl, key)
            if kind == 'scheduling':
                return transport.scheduling_aggregate(
                    grads, gains, p_w, fl, key)
            if kind == 'error_free':
                return transport.error_free_aggregate(
                    grads, fl, key, round_idx=round_idx)
            raise ValueError(kind)

        self._run_transport = run_transport

        if fl.allocation_backend == 'jax':
            dim = self.dim
            method = fl.allocator
            max_iters = fl.allocation_max_iters or 6
            alloc_tol = fl.allocation_tol or 1e-5
            early_exit = fl.allocation_early_exit

            def alloc_on_device(grads, gbar, gains, p_w):
                """Steps 3–4 fully on-device: stats -> eq. (28) -> (q, p)."""
                g64 = grads.astype(jnp.float64)
                gb = gbar if gbar.ndim == 2 else jnp.broadcast_to(
                    gbar, grads.shape)
                gb64 = gb.astype(jnp.float64)
                g2 = jnp.sum(g64 ** 2, axis=1)
                gb2 = jnp.sum(gb64 ** 2, axis=1)
                v = jnp.sum(jnp.abs(g64) * gb64, axis=1)
                d2 = jax.vmap(
                    lambda g: quantize_mod.expected_quant_mse(
                        g, fl.quant_bits)
                )(grads.astype(jnp.float32)).astype(jnp.float64)
                prob = alloc_jax.problem_from_stats(
                    g2, gb2, v, d2, gains, p_w, dim, fl,
                    dtype=jnp.float64)

                def solved(_):
                    s = alloc_jax.solve_traceable(prob, method,
                                                  max_iters=max_iters,
                                                  tol=alloc_tol,
                                                  early_exit=early_exit)
                    return (s.alpha, s.beta, s.q, s.p, s.objective,
                            s.iters, s.exit_reason, s.steps, s.dual_rounds)

                def uniform(_):
                    s = alloc_jax.solve_traceable(prob, 'uniform')
                    return (s.alpha, s.beta, s.q, s.p, s.objective,
                            s.iters, s.exit_reason, s.steps, s.dual_rounds)

                if method == 'uniform':
                    (alpha, beta, q, p, obj, iters, reason, steps,
                     rounds) = uniform(None)
                else:
                    # no compensation history yet (round 0): optimizing
                    # against gbar=0 degenerates to alpha=1 / ghat=0
                    (alpha, beta, q, p, obj, iters, reason, steps,
                     rounds) = jax.lax.cond(jnp.max(gb2) > 0.0, solved,
                                            uniform, None)
                return (q.astype(jnp.float32), p.astype(jnp.float32),
                        alpha.astype(jnp.float32),
                        beta.astype(jnp.float32), obj, iters, reason,
                        steps, rounds)

            # traced (and always re-entered) under x64: the closed forms
            # overflow f32 — see allocation_jax's precision contract
            with jax.enable_x64():
                self._alloc_jax = jax.jit(alloc_on_device)

    # ------------------------------------------------------------------
    def _allocate(self, grads: np.ndarray, gbar: np.ndarray,
                  gains: Optional[np.ndarray] = None):
        """Steps 3–4: scalars uplink + PS solves eq. (28) (host NumPy)."""
        fl = self.fl
        self.host_solver_calls += 1
        gains = self.gains if gains is None else np.asarray(gains,
                                                           np.float64)
        g2 = np.sum(grads ** 2, axis=1)
        gb = gbar if gbar.ndim == 2 else np.broadcast_to(gbar, grads.shape)
        gb2 = np.sum(gb ** 2, axis=1)
        v = np.sum(np.abs(grads) * gb, axis=1)
        # exact expected quantization MSE (paper §V estimates delta by
        # simulation; the closed form is tighter than Lemma 2's bound)
        d2 = np.asarray(jax.vmap(
            lambda g: quantize_mod.expected_quant_mse(g, fl.quant_bits)
        )(jnp.asarray(grads, jnp.float32)))
        prob = alloc.problem_from_stats(
            g2, gb2, v, d2, gains, self.p_w, self.dim, fl)
        method = fl.allocator
        if float(gb2.max()) == 0.0:
            # no compensation history yet (round 0): optimizing against
            # gbar=0 degenerates to alpha=1 / ghat=0; use uniform this round
            method = 'uniform'
        if method == 'alternating':
            sol = alloc.solve(prob, 'alternating',
                              max_iters=fl.allocation_max_iters or 2)
        elif method == 'barrier':
            sol = alloc.solve(prob, 'barrier',
                              max_iters=fl.allocation_max_iters or 6)
        else:
            sol = alloc.solve(prob, 'uniform')
        stats = dict(g2=g2, gb2=gb2, v=v, d2=d2, prob=prob)
        return sol, stats

    # ------------------------------------------------------------------
    # fused rounds (FLConfig.round_fusion = 'eager' | 'scan')
    # ------------------------------------------------------------------
    def _fused_round_core(self):
        """The whole round as ONE traceable function.

        ``round_core(params, gbar, kr, z, st, n) -> (params', gbar', z',
        st', rec, loss_mean)``: per-client grads -> AR(1) fading step
        (when ``allocation_cadence='per_round'``) -> straggler-chain
        step (``st``, when ``dropout_rate > 0``) -> in-trace float32
        eq. (28) solve -> transport (round ``n`` as a traced scalar) ->
        update -> compensation roll -> condensed telemetry record.  No
        host value is consumed anywhere, so the body scans
        (`_run_fused`).

        The allocation guard against an empty compensation history is a
        ``lax.cond`` on ``max(gbar^2) > 0`` — the traced twin of the
        host path's ``float(gb2.max()) == 0.0`` check in
        :meth:`_allocate`, which would be a device->host sync here.
        """
        fl = self.fl
        kind = fl.transport
        dim = self.dim
        gains_j = jnp.asarray(self.gains, jnp.float32)
        p_w_j = jnp.asarray(self.p_w, jnp.float32)
        method = fl.allocator
        max_iters = fl.allocation_max_iters or 6
        alloc_tol = fl.allocation_tol or 1e-5
        early_exit = fl.allocation_early_exit
        per_round_gains = fl.allocation_cadence == 'per_round'
        allocating = kind in ('spfl', 'spfl_retx')
        dropout = fl.dropout_rate > 0.0
        population = self._population
        pop_key = self._pop_key
        ragged = population and fl.cohort_sampler == 'availability'
        n_shards = self.client_x.shape[0]

        def alloc_f32(grads, gbar, gains_n, p_w_n):
            """Steps 3–4 in-trace, float32 end to end (the f64 closed
            forms live behind an ``enable_x64`` host wrapper and cannot
            appear inside this f32 trace — see allocation_jax)."""
            gb = gbar if gbar.ndim == 2 else jnp.broadcast_to(
                gbar, grads.shape)
            g2 = jnp.sum(grads ** 2, axis=1)
            gb2 = jnp.sum(gb ** 2, axis=1)
            v = jnp.sum(jnp.abs(grads) * gb, axis=1)
            d2 = jax.vmap(
                lambda g: quantize_mod.expected_quant_mse(
                    g, fl.quant_bits))(grads)
            prob = alloc_jax.problem_from_stats(
                g2, gb2, v, d2, gains_n, p_w_n, dim, fl,
                dtype=jnp.float32)

            def solved(_):
                s = alloc_jax.solve_traceable(prob, method,
                                              max_iters=max_iters,
                                              tol=alloc_tol,
                                              early_exit=early_exit)
                return (s.q, s.p, s.objective, s.iters, s.exit_reason,
                        s.steps, s.dual_rounds)

            def uniform(_):
                s = alloc_jax.solve_traceable(prob, 'uniform')
                return (s.q, s.p, s.objective, s.iters, s.exit_reason,
                        s.steps, s.dual_rounds)

            if method == 'uniform':
                return uniform(None)
            # no compensation history yet (round 0): optimizing against
            # gbar=0 degenerates to alpha=1 / ghat=0 — fall back to
            # uniform WITHOUT a host sync
            return jax.lax.cond(jnp.max(gb2) > 0.0, solved, uniform, None)

        def round_core(params, gbar, kr, z, st, n):
            # every stage runs under its obs/ scope (obs.trace.STAGES),
            # which names its device ops in the compiled program
            if population:
                # cohort gather: O(cohort) draws keyed off the per-round
                # key kr (identical across none/eager/scan dispatch) and
                # the static population key — per-device geometry, power
                # class and shadowing are lazily materialized for the
                # sampled ids only
                with stage_scope('fading'):
                    cohort = pop.sample_cohort(kr, pop_key, fl)
                    shards = pop.shard_ids(cohort.ids, n_shards)
                    xs = jnp.take(self.client_x, shards, axis=0)
                    ys = jnp.take(self.client_y, shards, axis=0)
                    present = cohort.present if ragged else None
                    p_w_n = cohort.p_w
                    byz_n = (pop.byzantine_ids(pop_key, cohort.ids,
                                               fl.attack_frac)
                             if fl.attack != 'none' else None)
            else:
                cohort = None
                xs, ys = self.client_x, self.client_y
                present, p_w_n, byz_n = None, p_w_j, None

            with stage_scope('grad'):
                losses, grads = self._per_client_grads(params, xs, ys)
                loss_mean = jnp.mean(losses)

            with stage_scope('fading'):
                if population:
                    # shadowing is stateless in population mode — keyed
                    # by (device id, round n), so a device's track is the
                    # same whether or not it was sampled in between
                    # (population.shadow_at); the z carry passes through
                    z2 = z
                    gains_n = pop.cohort_gains(pop_key, cohort.ids, n, fl,
                                               shadowing=per_round_gains)
                elif per_round_gains and allocating:
                    z2 = channel.shadow_step(
                        jax.random.fold_in(kr, 0x5AD0), z)
                    gains_n = channel.shadow_gains(gains_j, z2)
                else:
                    z2 = z
                    gains_n = gains_j

                # straggler chain: its own fold of the round key, so
                # eager, scan and the host loop draw bit-identical
                # dropouts and the existing streams (quantizer, channel)
                # are unperturbed
                if dropout:
                    st2, s_active = adversary.straggler_step(
                        jax.random.fold_in(kr, adversary.STRAGGLER_FOLD),
                        st, fl.dropout_rate, fl.straggler_stickiness)
                else:
                    st2, s_active = st, None
                # arrivals (ragged cohorts) compose with in-round stalls
                active = pop.combine_active(present, s_active)

            obj = iters = reason = steps = rounds = None
            if allocating:
                with stage_scope('alloc_solve'):
                    q, p, obj, iters, reason, steps, rounds = alloc_f32(
                        grads, gbar, gains_n, p_w_n)
            else:
                q = jnp.ones(self.K)
                p = jnp.ones(self.K)

            ghat, diag = self._run_transport(kind, grads, gbar, q, p,
                                             kr, n, active, byz_n)

            with stage_scope('update'):
                new_params = self._apply_update(params, ghat)
                if fl.compensation == 'last_global':
                    gbar2 = jnp.abs(ghat)
                elif fl.compensation == 'last_local':
                    gbar2 = jnp.abs(grads)
                elif fl.compensation == 'seeded_random':
                    gbar2 = jnp.abs(jax.random.normal(
                        jax.random.fold_in(
                            jax.random.PRNGKey(fl.seed + 99), n),
                        (dim,))) * 0.01
                else:                # zeros: leave as-is
                    gbar2 = gbar

            with stage_scope('telemetry'):
                rec = diag.with_allocation(
                    q, p, objective=obj, round_idx=n, iters=iters,
                    exit_reason=reason, steps=steps,
                    dual_rounds=rounds).condensed()
                if population:
                    rec = rec._replace(cohort_ids=cohort.ids)
            return new_params, gbar2, z2, st2, rec, loss_mean

        return round_core

    def _fused_round_body(self):
        """Scan body: carry = (params, gbar, key, z, straggler, ring)
        — the ring stays LAST; x = round index (traced uint32); y =
        mean client loss of the round."""
        round_core = self._fused_round_core()

        def round_body(carry, n):
            params, gbar, key, z, st, ring = carry
            key, kr = jax.random.split(key)
            params2, gbar2, z2, st2, rec, loss_mean = round_core(
                params, gbar, kr, z, st, n)
            # the traceable push, NOT the donated jitted wrapper — the
            # ring is scan carry, donation is the dispatcher's business
            with stage_scope('telemetry'):
                ring2 = obs_ring.ring_push(ring, rec)
            return (params2, gbar2, key, z2, st2, ring2), loss_mean

        return round_body

    def _fused_init_carry(self, seg_len: int):
        """Initial scan carry.  The telemetry ring is sized to the
        segment (one slot per round — no intra-segment wrap possible)
        and built from an ``eval_shape`` prototype of the round body's
        record, so no round runs before the first dispatch."""
        round_core = self._fused_round_core()
        z0 = channel.shadow_init(
            jax.random.fold_in(jax.random.PRNGKey(self._seed), 0x0FAD),
            self.K)
        st0 = self._straggler
        rec_sds = jax.eval_shape(
            lambda p_, g_, k_, z_, s_, n_: round_core(
                p_, g_, k_, z_, s_, n_)[4],
            self.params, self.gbar, self.key, z0, st0, jnp.uint32(0))
        ring = obs_ring.ring_init_abstract(rec_sds, seg_len)
        return (self.params, self.gbar, self.key, z0, st0, ring)

    def _run_fused(self, n_rounds: int, eval_every: int,
                   compute_bound: bool) -> FLHistory:
        """Segment-dispatched run: 'scan' issues ONE ``lax.scan`` per
        telemetry segment, 'eager' one jitted round-body call per round
        (same traced body — the integer-bit-exact reference for 'scan').

        Host syncs happen ONLY at segment boundaries: one ring flush
        (single ``device_get``) + the global eval.  ``eval_every`` is
        therefore quantized to segment boundaries; every boundary both
        flushes and evaluates, and the final ragged segment drains its
        tail, so no round's telemetry is dropped or double-flushed
        whatever ``telemetry_flush_every`` divides.
        """
        fl = self.fl
        kind = fl.transport
        if fl.round_fusion not in ('eager', 'scan'):
            raise ValueError(f'round_fusion must be none|eager|scan, '
                             f'got {fl.round_fusion!r}')
        if compute_bound:
            raise ValueError("compute_bound=True requires "
                             "round_fusion='none' (the Theorem-1 bound "
                             "needs host-side per-round stats)")
        if kind in ('spfl', 'spfl_retx') and fl.allocation_backend != 'jax':
            raise ValueError("round_fusion requires "
                             "allocation_backend='jax' on allocating "
                             "transports (eq. (28) must solve in-trace)")
        hist = FLHistory()
        span_mark = self.trace.mark()   # the sink's spans: this run's
        flush_every = max(1, fl.telemetry_flush_every)
        seg_len = fl.scan_segment_rounds or flush_every
        sink = (JsonlSink(fl.telemetry_path,
                          run_manifest(fl, extra={
                              'driver': 'fl_loop',
                              'round_fusion': fl.round_fusion}))
                if fl.telemetry_path else None)
        packed_agreement = (fl.wire == 'packed'
                            and kind in ('spfl', 'spfl_retx', 'error_free'))

        round_body = self._fused_round_body()
        carry = self._fused_init_carry(seg_len)
        if fl.round_fusion == 'scan':
            seg_fn = jax.jit(
                lambda c, ns: jax.lax.scan(round_body, c, ns))
        else:
            body_jit = jax.jit(round_body)

        start = self._round
        done = 0
        while done < n_rounds:
            m = min(seg_len, n_rounds - done)
            ns = jnp.arange(start + done, start + done + m,
                            dtype=jnp.uint32)
            t0 = time.time()
            with self.trace.span('fused_segment'):
                if fl.round_fusion == 'scan':
                    carry, seg_losses = seg_fn(carry, ns)
                else:
                    losses_l = []
                    for i in range(m):
                        carry, lm = body_jit(carry, ns[i])
                        losses_l.append(lm)
                    seg_losses = jnp.stack(losses_l)

            # ---- segment boundary: the run's only host sync points ----
            params, gbar, key, z, st, ring = carry
            recs, ring = obs_ring.flush(ring)        # ONE device_get
            carry = (params, gbar, key, z, st, ring)
            for rec in recs:
                row = obs_record.to_row(rec)
                hist.payload_bits.append(row['payload_bits'])
                hist.q_mean.append(row['q_mean'])
                hist.p_mean.append(row['p_mean'])
                hist.sign_ok_frac.append(row['sign_ok_frac'])
                hist.mod_ok_frac.append(row['mod_ok_frac'])
                if packed_agreement:
                    hist.sign_agreement.append(row['sign_agreement'])
                hist.alloc_iters.append(row['alloc_iters'])
                hist.alloc_steps.append(row['alloc_steps'])
                hist.alloc_dual_rounds.append(row['alloc_dual_rounds'])
                hist.alloc_exit_reason.append(row['alloc_exit_reason'])
                hist.retransmissions.append(row['retransmissions'])
                if fl.dropout_rate > 0.0 or (
                        self._population
                        and fl.cohort_sampler == 'availability'):
                    hist.participation_frac.append(
                        row['participation_frac'])
                if fl.screen:
                    hist.suspect_frac.append(row['suspect_frac'])
                self.metrics.observe_round(row)
                if sink is not None:
                    sink.write_round(row)
            prev_loss = float(seg_losses[-1])
            loss, acc = self._global_metrics(
                params, self.client_x, self.client_y,
                self.test_x, self.test_y)
            hist.loss.append(float(loss))
            hist.test_acc.append(float(acc))
            hist.loss_delta.append(float(loss) - prev_loss)
            wall = time.time() - t0
            # eq. (28) is fused into the round dispatch; there is no
            # separately timeable host alloc stage
            hist.alloc_time_s.extend([0.0] * m)
            hist.round_time_s.extend([wall / m] * m)
            done += m

        self.params, self.gbar, self.key = carry[0], carry[1], carry[2]
        self._straggler = carry[4]
        self._round += n_rounds
        self.metrics.observe_alloc(host_solver_calls=self.host_solver_calls)
        if sink is not None:
            sink.write_spans(self.trace.summary(since=span_mark))
            sink.write_compile(obs_trace.compile_counters())
            sink.write_metrics(self.metrics.snapshot())
            sink.close()
        return hist

    # ------------------------------------------------------------------
    def run(self, n_rounds: int, eval_every: int = 1,
            compute_bound: bool = False) -> FLHistory:
        if self.fl.round_fusion != 'none':
            return self._run_fused(n_rounds, eval_every, compute_bound)
        hist = FLHistory()
        span_mark = self.trace.mark()   # the sink's spans: this run's
        fl = self.fl
        kind = fl.transport
        if compute_bound and fl.allocation_backend == 'jax':
            # the Theorem-1 bound needs the host-side problem/stats the
            # on-device path deliberately never materializes — fail loud
            # instead of silently returning an empty hist.bound
            raise ValueError("compute_bound=True requires "
                             "allocation_backend='numpy'")
        # per-round block-fading gains (seeded off the run seed, so a
        # fixed-seed run is reproducible end to end); population mode
        # evolves shadowing lazily per cohort instead (pop.shadow_at)
        traj = None
        if fl.allocation_cadence == 'per_round' and not self._population:
            traj = channel.block_fading_trajectory(
                jax.random.fold_in(jax.random.PRNGKey(self._seed), 0x0FAD),
                jnp.asarray(self.gains, jnp.float32), n_rounds)
        gains_j = jnp.asarray(self.gains, jnp.float32)
        p_w_j = jnp.asarray(self.p_w, jnp.float32)
        pop_mode = self._population
        ragged = pop_mode and fl.cohort_sampler == 'availability'
        n_shards = self.client_x.shape[0]

        # --- telemetry plumbing (repro.obs): per-round records accumulate
        # in an on-device ring and cross to the host only at flush, so a
        # non-flush round's telemetry cost is one async ring-push dispatch
        flush_every = max(1, fl.telemetry_flush_every)
        ring = None
        sink = (JsonlSink(fl.telemetry_path,
                          run_manifest(fl, extra={'driver': 'fl_loop'}))
                if fl.telemetry_path else None)
        packed_agreement = (fl.wire == 'packed'
                            and kind in ('spfl', 'spfl_retx', 'error_free'))

        def _flush_telemetry():
            nonlocal ring
            if ring is None:
                return
            recs, ring = obs_ring.flush(ring)   # ONE device_get
            for rec in recs:
                row = obs_record.to_row(rec)
                hist.payload_bits.append(row['payload_bits'])
                hist.q_mean.append(row['q_mean'])
                hist.p_mean.append(row['p_mean'])
                hist.sign_ok_frac.append(row['sign_ok_frac'])
                hist.mod_ok_frac.append(row['mod_ok_frac'])
                if packed_agreement:
                    # exactly one entry per round on the packed wire — NaN
                    # when no sign packet survived or votes are unavailable
                    # (K > 32 exceeds the vote word) — so the list stays
                    # aligned with the other per-round histories
                    hist.sign_agreement.append(row['sign_agreement'])
                hist.alloc_iters.append(row['alloc_iters'])
                hist.alloc_steps.append(row['alloc_steps'])
                hist.alloc_dual_rounds.append(row['alloc_dual_rounds'])
                hist.alloc_exit_reason.append(row['alloc_exit_reason'])
                hist.retransmissions.append(row['retransmissions'])
                if fl.dropout_rate > 0.0 or (
                        self._population
                        and fl.cohort_sampler == 'availability'):
                    hist.participation_frac.append(
                        row['participation_frac'])
                if fl.screen:
                    hist.suspect_frac.append(row['suspect_frac'])
                self.metrics.observe_round(row)
                if sink is not None:
                    sink.write_round(row)

        for n in range(n_rounds):
            t0 = time.time()
            self.key, kr = jax.random.split(self.key)
            if pop_mode:
                # same per-round key the fused body uses, so all three
                # dispatch modes sample bit-identical cohorts
                cohort = pop.sample_cohort(kr, self._pop_key, fl)
                shards = pop.shard_ids(cohort.ids, n_shards)
                xs = jnp.take(self.client_x, shards, axis=0)
                ys = jnp.take(self.client_y, shards, axis=0)
                present = cohort.present if ragged else None
                byz_n = (pop.byzantine_ids(self._pop_key, cohort.ids,
                                           fl.attack_frac)
                         if fl.attack != 'none' else None)
            else:
                cohort, present, byz_n = None, None, None
                xs, ys = self.client_x, self.client_y
            # straggler chain: same fold of the same round key as the
            # fused body, so host-loop and scanned rounds drop the same
            # clients bit-for-bit
            if fl.dropout_rate > 0.0:
                self._straggler, s_active = adversary.straggler_step(
                    jax.random.fold_in(kr, adversary.STRAGGLER_FOLD),
                    self._straggler, fl.dropout_rate,
                    fl.straggler_stickiness)
            else:
                s_active = None
            active = pop.combine_active(present, s_active)
            losses, grads = self._per_client_grads(self.params, xs, ys)

            ta = time.time()
            alloc_obj = alloc_iters = alloc_reason = alloc_steps = None
            alloc_rounds = None
            with self.trace.span('alloc_solve'):
                if kind in ('spfl', 'spfl_retx'):
                    if pop_mode:
                        gains_n = pop.cohort_gains(
                            self._pop_key, cohort.ids,
                            jnp.uint32(self._round), fl,
                            shadowing=fl.allocation_cadence == 'per_round')
                        p_w_n = cohort.p_w
                    else:
                        gains_n = gains_j if traj is None else traj[n]
                        p_w_n = p_w_j
                    if fl.allocation_backend == 'jax':
                        # one on-device dispatch, no host round-trip (the
                        # x64 re-entry keeps the jit cache key stable)
                        with jax.enable_x64():
                            (q, p, _, _, alloc_obj, alloc_iters,
                             alloc_reason, alloc_steps,
                             alloc_rounds) = self._alloc_jax(
                                grads, self.gbar, gains_n, p_w_n)
                        sol, stats = None, None
                    else:
                        grads_np = np.asarray(grads, np.float64)
                        sol, stats = self._allocate(
                            grads_np, np.asarray(self.gbar),
                            None if traj is None
                            else np.asarray(gains_n, np.float64))
                        q, p = jnp.asarray(sol.q), jnp.asarray(sol.p)
                        alloc_obj = sol.objective
                        alloc_iters = jnp.int32(
                            sol.info.get('iters_used', 0))
                        alloc_reason = jnp.int32(
                            sol.info.get('exit_reason', 0))
                        objs = sol.info.get('objectives', [])
                        if len(objs) >= 2:
                            self.metrics.observe_alloc(
                                outer_residual=abs(objs[-1] - objs[-2]))
                else:
                    sol, stats, q, p = None, None, jnp.ones(self.K), jnp.ones(self.K)
            alloc_t = time.time() - ta

            ghat, diag = self._run_transport(
                kind, grads, self.gbar, q, p, kr,
                jnp.uint32(self._round), active, byz_n)

            if compute_bound and sol is not None:
                gsum = np.asarray(convergence.g_value_from_probs(
                    stats['prob'].coef, sol.p, sol.q))
                inp = convergence.bound_inputs_from_grads(
                    grads_np, np.asarray(self.gbar))
                b = convergence.one_step_bound(
                    fl.learning_rate, self.K, inp['g_global2'],
                    inp['gb2'], inp['g2'], inp['e2'], inp['v'], gsum)
                hist.bound.append(float(b))

            with self.trace.span('update'):
                new_params = self._apply_update(self.params, ghat)

            # roll compensation
            if fl.compensation == 'last_global':
                self.gbar = jnp.abs(ghat)
            elif fl.compensation == 'last_local':
                self.gbar = jnp.abs(grads)
            elif fl.compensation == 'seeded_random':
                self.gbar = jnp.abs(jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(fl.seed + 99), n),
                    (self.dim,))) * 0.01
            # zeros: leave as-is
            self.params = new_params
            self._round += 1

            # enrich the transport record with the round's allocation
            # state and push it into the device ring — a pure _replace
            # plus one jitted dynamic-update; no host transfer here
            rec = diag.with_allocation(
                q, p, objective=alloc_obj,
                round_idx=jnp.uint32(self._round - 1),
                iters=alloc_iters, exit_reason=alloc_reason,
                steps=alloc_steps, dual_rounds=alloc_rounds).condensed()
            if pop_mode:
                rec = rec._replace(cohort_ids=cohort.ids)
            if ring is None:
                ring = obs_ring.ring_init(rec, flush_every)
            ring = obs_ring.push(ring, rec)

            if n % eval_every == 0 or n == n_rounds - 1:
                prev_loss = float(jnp.mean(losses))
                loss, acc = self._global_metrics(
                    self.params, self.client_x, self.client_y,
                    self.test_x, self.test_y)
                hist.loss.append(float(loss))
                hist.test_acc.append(float(acc))
                hist.loss_delta.append(float(loss) - prev_loss)
            if (n + 1) % flush_every == 0 or n == n_rounds - 1:
                _flush_telemetry()
            hist.alloc_time_s.append(alloc_t)
            hist.round_time_s.append(time.time() - t0)
        self.metrics.observe_alloc(host_solver_calls=self.host_solver_calls)
        if sink is not None:
            sink.write_spans(self.trace.summary(since=span_mark))
            sink.write_compile(obs_trace.compile_counters())
            sink.write_metrics(self.metrics.snapshot())
            sink.close()
        return hist


# ---------------------------------------------------------------------------
def build_simulator(fl: FLConfig, per_device: int = 500,
                    n_test: int = 2000, iid: bool = False,
                    seed: Optional[int] = None) -> FLSimulator:
    """Paper §V setup: partitioned (synthetic-)CIFAR + CNN + wireless cell."""
    from repro.data import (
        dirichlet_partition, iid_partition, load_image_dataset,
        stack_client_data,
    )
    seed = fl.seed if seed is None else seed
    (x, y), (tx, ty) = load_image_dataset(seed=seed)
    # population mode materializes S data SHARDS, not N device datasets:
    # virtual device d reads shard d mod S (population.shard_ids) under
    # the partitioners' with-replacement contract (data/partition.py)
    k = fl.population_shards if fl.population_n > 0 else fl.n_devices
    if iid:
        parts = iid_partition(y, k, per_device, seed)
    else:
        parts = dirichlet_partition(y, k, per_device,
                                    fl.dirichlet_alpha, seed)
    cx, cy = stack_client_data(x, y, parts)
    return FLSimulator(fl, cx, cy, tx[:n_test], ty[:n_test], seed=seed)
