"""LLM-scale federated train step — SP-FL as the gradient transport of a
data-parallel training system (DESIGN.md §3).

The FL client axis is the mesh's (pod, data) extent: ``jax.vmap(jax.grad)``
over a leading client axis of the batch produces stacked per-client
gradients whose client dim shards over ('pod','data') and whose parameter
dims shard over 'model' — so the K× gradient memory is fully distributed.
The transport then runs vectorized over clients and its client-axis
reduction is what GSPMD lowers to the cross-client all-reduce (the
"uplink").  With ``fl.wire='packed'`` that reduction happens in the
packed domain: the per-leaf collective consumes the bit-packed (K, W)
uint32 payload words through the decode-once kernel
(``repro.kernels.ops.spfl_aggregate_packed``), so the wire traffic is
~(1+b) bits/coordinate instead of the f32 (or bf16, via
``fl.uplink_reduce_dtype``) leaves of the analytic path.

With ``fl.collective='sharded'`` (pass the mesh into
``make_fl_train_step``) the packed reduction never gathers client
payloads: each device runs the decode-once kernel over its own clients'
(K_local, W) words and one psum of d-float partials finishes each leaf
(``kernels.ops.spfl_aggregate_packed_sharded``) — the default 'gather'
lowering would instead all-gather the K*W payload words per leaf, which
forfeits the packed byte win exactly at mesh scale.

The wireless channel success probabilities (q, p) enter as *inputs*: the
hierarchical allocator (repro.core.allocation) runs host-side between
rounds on the per-client scalars this step also returns — exactly
Algorithm 2 steps 4–5 with a one-round-stale norm report (noted in
DESIGN.md §5).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import adversary
from repro import population as pop
from repro.configs.base import FLConfig, ModelConfig
from repro.core import allocation_jax as alloc_jax
from repro.core import channel
from repro.core import transport as tr
from repro.models import transformer as tf
from repro.obs import ringbuf as obs_ring
from repro.obs.record import round_scalars
from repro.obs.trace import stage_scope
from repro.training.optimizer import Optimizer, sgd


def init_gbar(params) -> Any:
    """Compensation modulus tree (last_global style), fp32 zeros."""
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _adversary_closures(fl: FLConfig, k: Optional[int] = None):
    """Byzantine mask (run-constant closure) + per-round dropout draw for
    the LLM-scale step.  Unlike the host loop's sticky Gilbert process,
    the fused tree path draws participation i.i.d. per round from the
    round key — no extra scan-carry state, same STRAGGLER_FOLD stream.
    'labelflip' has no packet-level transform here (token labels are
    flipped at data setup by the host loop), so its mask stays unused
    inside the transport.  ``k`` overrides the client-axis width (the
    cohort width in population mode, where the slot-static byzantine
    mask is replaced by per-id membership — population.byzantine_ids)."""
    k = fl.n_devices if k is None else k
    byz = (adversary.byzantine_mask(fl.seed, k, fl.attack_frac)
           if fl.attack != 'none' and not fl.population_n else None)

    def draw_active(key):
        if fl.dropout_rate <= 0.0:
            return None
        return adversary.bernoulli_active(
            jax.random.fold_in(key, adversary.STRAGGLER_FOLD),
            k, fl.dropout_rate)

    return byz, draw_active


def client_batch_shapes(cfg: ModelConfig, n_clients: int,
                        global_batch: int, seq_len: int) -> Dict[str, Any]:
    """ShapeDtypeStructs of one training batch, client-major."""
    assert global_batch % n_clients == 0, (global_batch, n_clients)
    b = global_batch // n_clients
    shapes = {'tokens': jax.ShapeDtypeStruct(
        (n_clients, b, seq_len), jnp.int32)}
    if cfg.frontend == 'vision' and cfg.n_prefix_tokens:
        shapes['prefix'] = jax.ShapeDtypeStruct(
            (n_clients, b, cfg.n_prefix_tokens, cfg.frontend_embed_dim),
            jnp.bfloat16)
    return shapes


def make_fl_train_step(cfg: ModelConfig, fl: FLConfig,
                       transport_kind: str = 'spfl', unroll: bool = False,
                       mesh=None):
    """Returns train_step(params, batch, gbar, q, p, key) ->
    (new_params, new_gbar, metrics).

    ``mesh`` is required when ``fl.collective='sharded'`` — the tree
    transports shard_map their per-leaf decode-once passes over its
    client axes (launch.mesh.client_axes) instead of letting GSPMD
    all-gather the packed payloads."""
    lr = fl.learning_rate
    if fl.collective == 'sharded' and mesh is None:
        raise ValueError("fl.collective='sharded' needs the mesh passed "
                         "into make_fl_train_step")
    byz_mask, draw_active = _adversary_closures(fl)

    def train_step(params, batch, gbar, q, p, key):
        def client_loss(params_, bk):
            return tf.loss_fn(params_, cfg, bk['tokens'], bk.get('prefix'),
                              unroll=unroll)

        def one(bk):
            return jax.value_and_grad(client_loss)(params, bk)

        losses, grads = jax.vmap(one)(batch)      # (K,), leaves (K, ...)

        if transport_kind == 'spfl':
            ghat, stats, diag = tr.spfl_aggregate_tree(
                grads, gbar, q, p, fl, key, wire=fl.wire,
                channel=fl.channel, mesh=mesh,
                attack=fl.attack, byz_mask=byz_mask,
                attack_scale=fl.attack_scale,
                active=draw_active(key), screen=fl.screen,
                screen_z=fl.screen_z,
                min_participation=fl.min_participation)
        elif transport_kind == 'error_free':
            ghat, stats, diag = tr.error_free_aggregate_tree(
                grads, fl, key, wire=fl.wire, mesh=mesh)
        else:
            raise ValueError(
                f'LLM-scale transport must be spfl|error_free, '
                f'got {transport_kind!r}')

        new_params = jax.tree.map(
            lambda pp, g: (pp.astype(jnp.float32)
                           - lr * g).astype(pp.dtype), params, ghat)
        new_gbar = jax.tree.map(lambda g: jnp.abs(g), ghat)
        # telemetry keys come from the shared RoundTelemetry serializer
        # (repro.obs.record.round_scalars — same names as the FLHistory
        # per-round lists), not a hand-rolled dict; the per-client vectors
        # tests and the host allocator consume ride alongside, and the
        # full record is returned under 'telemetry' for ring-buffering
        diag = diag.with_allocation(q, p)
        metrics = {
            'loss': jnp.mean(losses),
            'client_losses': losses,
            'g_norm_sq': stats['g2'],            # -> host allocator
            'g_min': stats['g_min'],
            'g_max': stats['g_max'],
            'sign_ok': diag.sign_ok,
            'mod_ok': diag.mod_ok,
            'telemetry': diag,
            **round_scalars(diag),
        }
        return new_params, new_gbar, metrics

    return train_step


def make_fused_fl_round(cfg: ModelConfig, fl: FLConfig,
                        optimizer: Optional[Optimizer] = None,
                        transport_kind: str = 'spfl',
                        unroll: bool = False, mesh=None):
    """The WHOLE Algorithm-2 round as one traceable function — the
    LLM-scale twin of ``fl_loop._fused_round_core``.

    Returns ``round_fn(params, opt_state, gbar, batch, gains, key,
    round_idx) -> (params', opt_state', gbar', rec, loss)``: per-client
    grads -> tree stats -> in-trace float32 eq. (28) solve -> tree
    transport (round index as a traced scalar into the PRNG stream) ->
    optimizer update -> compensation roll -> condensed telemetry record.
    No host value is consumed, so the function scans
    (:func:`make_fused_fl_scan`).

    Unlike the host driver's one-round-stale scalar report
    (launch/train.py), the fused solve sees the CURRENT round's exact
    per-client stats — including the exact v_k = <|g_k|, gbar> the host
    path can only approximate — because the gradients are already on
    device when eq. (28) is traced into the same dispatch.

    ``optimizer`` defaults to plain SGD at ``fl.learning_rate`` (the
    paper's eq. (6) update, identical to ``make_fl_train_step``'s
    inline step); its state rides the scan carry.
    """
    if fl.collective == 'sharded' and mesh is None:
        raise ValueError("fl.collective='sharded' needs the mesh passed "
                         "into make_fused_fl_round")
    if transport_kind not in ('spfl', 'error_free'):
        raise ValueError(
            f'LLM-scale transport must be spfl|error_free, '
            f'got {transport_kind!r}')
    if transport_kind == 'spfl' and fl.allocation_backend != 'jax':
        raise ValueError("fused rounds require allocation_backend='jax' "
                         "(eq. (28) must solve in-trace)")
    opt = optimizer if optimizer is not None else sgd(fl.learning_rate)
    population = fl.population_n > 0
    K = pop.cohort_size(fl) if population else fl.n_devices
    byz_mask, draw_active = _adversary_closures(fl, K)
    pop_key = pop.population_key(fl.seed) if population else None
    ragged = population and fl.cohort_sampler == 'availability'
    p_w = jnp.full((K,), fl.tx_power_w, jnp.float32)
    method = fl.allocator
    max_iters = fl.allocation_max_iters or 6
    alloc_tol = fl.allocation_tol or 1e-5
    early_exit = fl.allocation_early_exit

    def alloc_f32(grads, gbar, stats, gains, p_w_n):
        """In-trace tree-stats eq. (28): exact per-client g2/v, shared
        gb2 (the compensation tree is global at LLM scale), Lemma-2
        delta^2 — all float32, solved by ``solve_traceable``."""
        gb2s = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                   for g in jax.tree.leaves(gbar))
        gb2 = jnp.full((K,), gb2s)
        v = sum(
            jnp.sum(jnp.abs(g.astype(jnp.float32)).reshape(K, -1)
                    * b.astype(jnp.float32).reshape(1, -1), axis=1)
            for g, b in zip(jax.tree.leaves(grads), jax.tree.leaves(gbar)))
        d2 = tr.delta_sq_tree(stats, fl.quant_bits).astype(jnp.float32)
        prob = alloc_jax.problem_from_stats(
            stats['g2'], gb2, v, d2, gains, p_w_n, stats['dim'], fl,
            dtype=jnp.float32)

        def solved(_):
            s = alloc_jax.solve_traceable(prob, method,
                                          max_iters=max_iters,
                                          tol=alloc_tol,
                                          early_exit=early_exit)
            return s.q, s.p, s.objective, s.iters, s.exit_reason, s.steps

        def uniform(_):
            s = alloc_jax.solve_traceable(prob, 'uniform')
            return s.q, s.p, s.objective, s.iters, s.exit_reason, s.steps

        if method == 'uniform':
            return uniform(None)
        # round 0 (gbar = 0) degenerates to alpha=1/ghat=0: fall back
        # to uniform via lax.cond — no device->host sync in the guard
        return jax.lax.cond(gb2s > 0.0, solved, uniform, None)

    def round_fn(params, opt_state, gbar, batch, gains, key, round_idx,
                 cohort=None):
        def client_loss(params_, bk):
            return tf.loss_fn(params_, cfg, bk['tokens'], bk.get('prefix'),
                              unroll=unroll)

        def one(bk):
            return jax.value_and_grad(client_loss)(params, bk)

        # every stage runs under its obs/ scope (obs.trace.STAGES)
        with stage_scope('grad'):
            losses, grads = jax.vmap(one)(batch)
            loss_mean = jnp.mean(losses)
            stats = tr.tree_client_stats(grads)

        # population mode hands the sampled cohort in: per-device power
        # class, per-id byzantine membership and arrival raggedness all
        # derive from the cohort's global ids (lazily, O(cohort))
        with stage_scope('fading'):
            if cohort is not None:
                p_w_n = cohort.p_w
                byz_n = (pop.byzantine_ids(pop_key, cohort.ids,
                                           fl.attack_frac)
                         if fl.attack != 'none' else None)
                present = cohort.present if ragged else None
            else:
                p_w_n, byz_n, present = p_w, byz_mask, None
            active = pop.combine_active(present, draw_active(key))

        obj = iters = reason = steps = None
        if transport_kind == 'spfl':
            with stage_scope('alloc_solve'):
                q, p, obj, iters, reason, steps = alloc_f32(
                    grads, gbar, stats, gains, p_w_n)
            ghat, _, diag = tr.spfl_aggregate_tree(
                grads, gbar, q, p, fl, key, stats=stats, wire=fl.wire,
                channel=fl.channel, mesh=mesh, round_idx=round_idx,
                attack=fl.attack, byz_mask=byz_n,
                attack_scale=fl.attack_scale,
                active=active, screen=fl.screen,
                screen_z=fl.screen_z,
                min_participation=fl.min_participation)
        else:
            q = jnp.ones((K,))
            p = jnp.ones((K,))
            ghat, _, diag = tr.error_free_aggregate_tree(
                grads, fl, key, stats=stats, wire=fl.wire, mesh=mesh,
                round_idx=round_idx)

        with stage_scope('update'):
            new_params, new_opt = opt.update(ghat, opt_state, params)
            new_gbar = jax.tree.map(lambda g: jnp.abs(g), ghat)
        with stage_scope('telemetry'):
            rec = diag.with_allocation(q, p, objective=obj,
                                       round_idx=round_idx, iters=iters,
                                       exit_reason=reason,
                                       steps=steps).condensed()
            if cohort is not None:
                rec = rec._replace(cohort_ids=cohort.ids)
        return new_params, new_opt, new_gbar, rec, loss_mean

    return round_fn


def make_fused_fl_scan(cfg: ModelConfig, fl: FLConfig, base_gains,
                       batch_fn, optimizer: Optional[Optimizer] = None,
                       transport_kind: str = 'spfl', unroll: bool = False,
                       mesh=None):
    """Roll :func:`make_fused_fl_round` over whole segments with
    ``jax.lax.scan`` — N rounds per dispatch, zero host transfers
    between segment boundaries.

    Scan carry: ``(params, opt_state, gbar, key, shadow_z, ring)`` —
    optimizer state, compensation tree, the AR(1) block-fading state
    (advanced in-trace when ``allocation_cadence='per_round'``) and the
    on-device telemetry ring all live on device for the segment.

    ``batch_fn(n) -> batch`` must be traceable (e.g. a
    ``lax.dynamic_slice`` into a resident token pool keyed on the round
    index) — a host-side batch feed would reintroduce the per-round
    sync this path exists to remove.  In population mode
    (``fl.population_n > 0``) the signature becomes ``batch_fn(n, ids)
    -> batch``: the sampled cohort's global device ids select each
    slot's data (e.g. through ``population.shard_ids``), the cohort is
    sampled in-trace from the round key, its lazily-materialized gains
    replace ``base_gains`` (which may be ``None``), and the shadowing
    track is stateless (``population.shadow_at`` — keyed by device id
    and round, not carried).

    Returns ``(segment, init_carry)``:

    * ``segment(carry, ns)`` — scan the round body over the traced
      round-index vector ``ns`` (uint32); jit it once and reuse (a
      ragged final segment costs one extra compile).
    * ``init_carry(params, key, seg_len)`` — initial carry with the
      ring sized to ``seg_len`` (one slot per round: no intra-segment
      wrap) built from an ``eval_shape`` prototype, so nothing runs
      before the first dispatch.
    """
    opt = optimizer if optimizer is not None else sgd(fl.learning_rate)
    round_fn = make_fused_fl_round(cfg, fl, opt, transport_kind, unroll,
                                   mesh)
    population = fl.population_n > 0
    pop_key = pop.population_key(fl.seed) if population else None
    gains_j = (None if population
               else jnp.asarray(base_gains, jnp.float32))
    per_round_gains = (fl.allocation_cadence == 'per_round'
                       and transport_kind == 'spfl')

    def one_round(params, opt_state, gbar, key, z, kr, n):
        if population:
            # cohort gather inside the scan body: membership from the
            # round key (bit-identical to the eager dispatch), state
            # from the static population key — O(cohort), stateless
            with stage_scope('fading'):
                cohort = pop.sample_cohort(kr, pop_key, fl)
                gains_n = pop.cohort_gains(pop_key, cohort.ids, n, fl,
                                           shadowing=per_round_gains)
                z2, batch = z, batch_fn(n, cohort.ids)
        elif per_round_gains:
            with stage_scope('fading'):
                z2 = channel.shadow_step(jax.random.fold_in(kr, 0x5AD0),
                                         z)
                gains_n = channel.shadow_gains(gains_j, z2)
            cohort, batch = None, batch_fn(n)
        else:
            z2, gains_n = z, gains_j
            cohort, batch = None, batch_fn(n)
        params2, opt2, gbar2, rec, loss = round_fn(
            params, opt_state, gbar, batch, gains_n, kr, n, cohort)
        return params2, opt2, gbar2, z2, rec, loss

    def body(carry, n):
        params, opt_state, gbar, key, z, ring = carry
        key, kr = jax.random.split(key)
        params2, opt2, gbar2, z2, rec, loss = one_round(
            params, opt_state, gbar, key, z, kr, n)
        # the traceable push (the donated jitted wrapper cannot appear
        # inside a scan body)
        with stage_scope('telemetry'):
            ring2 = obs_ring.ring_push(ring, rec)
        return (params2, opt2, gbar2, key, z2, ring2), loss

    def init_carry(params, key, seg_len: int):
        opt_state = opt.init(params)
        gbar = init_gbar(params)
        z0 = channel.shadow_init(jax.random.fold_in(key, 0x0FAD),
                                 pop.cohort_size(fl) if population
                                 else fl.n_devices)
        rec_sds = jax.eval_shape(
            lambda p_, o_, g_, k_: one_round(
                p_, o_, g_, k_, z0, k_, jnp.uint32(0))[4],
            params, opt_state, gbar, key)
        ring = obs_ring.ring_init_abstract(rec_sds, seg_len)
        return (params, opt_state, gbar, key, z0, ring)

    def segment(carry, ns):
        return jax.lax.scan(body, carry, ns)

    return segment, init_carry


def make_standard_train_step(cfg: ModelConfig, fl: FLConfig,
                             unroll: bool = False):
    """Plain data-parallel step (batch (B, T), one global gradient).

    Used where classic client-resident-model FL is physically impossible —
    arctic-480b's experts are sharded over the client axes, so per-client
    full gradients do not exist (DESIGN.md §Arch-applicability).  The
    uplink is error-free; gradients are still stochastically quantized so
    the numerics match the FL path as closely as possible.
    """
    lr = fl.learning_rate

    def train_step(params, batch, key):
        def loss(params_):
            return tf.loss_fn(params_, cfg, batch['tokens'],
                              batch.get('prefix'), unroll=unroll)

        loss_val, grads = jax.value_and_grad(loss)(params)
        new_params = jax.tree.map(
            lambda pp, g: (pp.astype(jnp.float32)
                           - lr * g.astype(jnp.float32)).astype(pp.dtype),
            params, grads)
        g2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        return new_params, {'loss': loss_val, 'g_norm_sq': g2}

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        return tf.loss_fn(params, cfg, batch['tokens'], batch.get('prefix'))
    return eval_step
