"""Sharded packed-domain collective: gathered vs sharded traffic and
wall-clock on the forced 8-device CPU host mesh.

The acceptance numbers for the sharded collective (ISSUE 4):

* per-leaf cross-device traffic — the gathered lowering all-gathers the
  K*(Ws+Wm) packed payload words of every client, the sharded lowering
  psums one l-float f32 partial (+ one l-int32 vote partial on the flat
  path when votes ride along): at K=32, l=2^16 the sharded bytes are
  <= 1/4 of the gathered all-gather, asserted below (the accounting is
  analytic and machine-independent);
* parity: the sharded flat transport's update matches the gathered one
  (integers bit-exact, f32 within the documented ulp contract) on the
  live mesh — the deep grid lives in tests/test_distributed_packed.py;
* wall-clock of the flat spfl round under both collectives with
  client-sharded inputs (CPU numbers — the psum-vs-gather traffic win
  needs real interconnect to show up in time, but the lowering and the
  byte accounting are the same on TPU).

Needs >= 2 devices to exercise the cross-shard psum: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the module sets
it when imported before jax initializes; under `run.py` with earlier
suites the backend may already be up — rows then record the real device
count).  BENCH_SMOKE=1 shrinks l (K stays 32: the byte ratio is K/8).
"""
from __future__ import annotations

import functools
import os

_flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()

import time

import jax
import jax.numpy as jnp
import numpy as np

from common import SMOKE, emit

from repro.configs.base import FLConfig
from repro.core import transport as TR
from repro.launch import shardings as SH
from repro.launch.mesh import make_mesh
from repro.wire import format as fmt

K = 32
L = 1 << 12 if SMOKE else 1 << 16


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def main() -> None:
    fl = FLConfig()
    bits = fl.quant_bits
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ('data',))
    emit('dist_mesh', 0.0, f'{n_dev} devices as (data={n_dev}) '
         f'[{jax.default_backend()}]')

    # --------------------------- per-leaf cross-device byte accounting
    payload_words = fmt.payload_words(L, 1) + fmt.payload_words(L, bits)
    gathered_b = K * payload_words * 4        # every client's packed words
    sharded_b = L * 4                         # ONE f32 partial psum
    votes_b = L * 4                           # int32 vote partial (flat, K<=32/shard)
    emit('dist_bytes_gathered', 0.0,
         f'{gathered_b} B (all-gather of K={K} x {payload_words} payload '
         f'words, l={L})')
    emit('dist_bytes_sharded', 0.0,
         f'{sharded_b} B (l-float f32 partial psum; per leaf — tree '
         f'leaves carry no votes)')
    emit('dist_bytes_sharded_votes', 0.0,
         f'{sharded_b + votes_b} B (+l-int32 vote partial, flat path)')
    emit('dist_bytes_ratio', 0.0,
         f'sharded = 1/{gathered_b / sharded_b:.2f} of gathered '
         f'(target <= 1/4 at K=32)')
    assert sharded_b * 4 <= gathered_b, (sharded_b, gathered_b)

    # ------------------------------- flat spfl round, both collectives
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (K, L)) * 0.02
    grads = jnp.where(g == 0, 1e-4, g)
    gbar = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (L,)))
    q = jnp.linspace(0.5, 0.95, K)
    p = jnp.linspace(0.4, 0.9, K)
    grads = jax.device_put(grads, SH.client_sharding(mesh))
    qs = jax.device_put(q, SH.client_sharding(mesh, ndim=1))
    ps = jax.device_put(p, SH.client_sharding(mesh, ndim=1))

    outs = {}
    for coll in ('gather', 'sharded'):
        agg = jax.jit(lambda kk, c=coll: TR.spfl_aggregate(
            grads, gbar, qs, ps, bits, fl.b0_bits, kk, wire='packed',
            collective=c, mesh=mesh if c == 'sharded' else None))
        t = _time(lambda kk: agg(kk)[0], jax.random.PRNGKey(5))
        ghat, diag = agg(jax.random.PRNGKey(5))
        outs[coll] = (ghat, diag)
        emit(f'dist_spfl_{coll}', 1e6 * t,
             f'K={K} l={L} payload_bits={float(diag.payload_bits):.0f}')

    # parity on the live mesh (integers bit-exact, f32 within ulp)
    gh_g, d_g = outs['gather']
    gh_s, d_s = outs['sharded']
    wmax = float(jnp.max(jnp.abs(gh_g - gh_s)))
    w = d_g.sign_ok.astype(jnp.float32) / qs       # the 1/q weights
    atol = 4 * np.finfo(np.float32).eps * float(jnp.sum(
        w * jnp.maximum(jnp.max(jnp.abs(grads), axis=1),
                        jnp.max(gbar)))) / K
    votes_match = (d_g.sign_votes is None and d_s.sign_votes is None) or \
        bool(jnp.array_equal(d_g.sign_votes, d_s.sign_votes))
    emit('dist_parity_f32', 0.0,
         f'max|gather-sharded|={wmax:.2e} (ulp budget {atol:.2e})')
    emit('dist_parity_votes', 0.0, f'bit-exact={votes_match}')
    assert votes_match
    assert bool(jnp.array_equal(d_g.sign_ok, d_s.sign_ok))
    if not SMOKE:
        assert wmax <= atol, (wmax, atol)

    # --------------------------------- bitlevel round, both collectives
    agg_sharded = None
    for coll in ('gather', 'sharded'):
        agg = jax.jit(lambda kk, c=coll: TR.spfl_aggregate(
            grads, gbar, qs, ps, bits, fl.b0_bits, kk, wire='packed',
            channel='bitlevel', collective=c,
            mesh=mesh if c == 'sharded' else None))
        # block on (ghat, diag): the telemetry record is materialized in
        # the baseline too, so the overhead row isolates the ring layer
        t = _time(agg, jax.random.PRNGKey(7))
        _, diag = agg(jax.random.PRNGKey(7))
        if coll == 'sharded':
            agg_sharded = agg
        emit(f'dist_spfl_bitlevel_{coll}', 1e6 * t,
             f'sign_ok={int(jnp.sum(diag.sign_ok))}/{K} '
             f'flips={int(jnp.sum(diag.sign_flips))}')

    # ------- telemetry: overhead row + JSONL emission (bitlevel+sharded)
    # the obs acceptance run: every round's RoundTelemetry accumulates in
    # the on-device ring inside the jitted round (< 5% wall-clock), and
    # the flushed rows land in a JSONL file with the full run manifest —
    # CI's bench-smoke uploads telemetry/ as a workflow artifact
    import dataclasses

    from repro.obs import JsonlSink, run_manifest, to_row
    from repro.obs import ringbuf as obs_ring

    # ring donated -> in-place dynamic update (see obs.ringbuf.push);
    # the timing loop must thread the returned ring
    @functools.partial(jax.jit, donate_argnums=0)
    def round_tel(ring_, kk, i):
        ghat, diag = TR.spfl_aggregate(
            grads, gbar, qs, ps, bits, fl.b0_bits, kk, wire='packed',
            channel='bitlevel', collective='sharded', mesh=mesh)
        rec = diag.with_allocation(qs, ps, round_idx=i).condensed()
        return ghat, obs_ring.ring_push(ring_, rec)

    _, d0 = jax.jit(lambda kk: TR.spfl_aggregate(
        grads, gbar, qs, ps, bits, fl.b0_bits, kk, wire='packed',
        channel='bitlevel', collective='sharded',
        mesh=mesh))(jax.random.PRNGKey(7))
    ring = obs_ring.ring_init(
        d0.with_allocation(qs, ps, round_idx=jnp.uint32(0)).condensed(), 16)
    kk7 = jax.random.PRNGKey(7)
    # two warmups: the first donated call can change the ring buffer's
    # layout/sharding, recompiling once more on the second call
    for _ in range(2):
        ghat, ring = round_tel(ring, kk7, jnp.uint32(0))
        jax.block_until_ready(ghat)
    # re-time the bare round back to back with the telemetry round (same
    # reps) — reusing the earlier row's 5-rep sample makes the delta all
    # box noise on a shared CPU
    reps = 10
    t_bare = _time(agg_sharded, kk7, reps=reps)
    t0 = time.time()
    for _ in range(reps):
        ghat, ring = round_tel(ring, kk7, jnp.uint32(0))
    jax.block_until_ready(ghat)
    t_tel = (time.time() - t0) / reps
    ovh = 100.0 * (t_tel - t_bare) / t_bare
    emit('dist_telemetry_overhead',
         1e6 * max(t_tel - t_bare, 0.0),
         f'{ovh:+.2f}% bitlevel+sharded round wall-clock with in-jit '
         f'ring push (target < 5%)')

    _, ring = obs_ring.flush(ring)       # drop the timing-loop pushes
    n_rounds = 4
    for i in range(n_rounds):
        _, ring = round_tel(ring, jax.random.fold_in(key, 200 + i),
                            jnp.uint32(i))
    recs, ring = obs_ring.flush(ring)
    fl_run = dataclasses.replace(fl, n_devices=K, wire='packed',
                                 channel='bitlevel', collective='sharded')
    out_path = os.path.join(os.path.dirname(__file__), '..', 'telemetry',
                            'bench_distributed.jsonl')
    with JsonlSink(out_path, run_manifest(
            fl_run, mesh=mesh,
            extra={'driver': 'bench_distributed'})) as sink:
        for rec in recs:
            sink.write_round(to_row(rec))
    emit('dist_telemetry_jsonl', 0.0,
         f'{len(recs)} rounds + manifest -> telemetry/'
         f'bench_distributed.jsonl')

    # ------------- fused multi-round scan over the sharded collective
    # (ISSUE 7) the bitlevel+sharded round (transport + ring push +
    # update + compensation roll) scanned N rounds per dispatch vs the
    # same body dispatched per round — the LLM-scale twin of the
    # wire-level fused_scan rows.
    n_scan = 4 if SMOKE else 16

    def round_body(carry, n):
        params_, gbar_, key_, ring_ = carry
        key_, kr = jax.random.split(key_)
        ghat, diag = TR.spfl_aggregate(
            grads, gbar_, qs, ps, bits, fl.b0_bits, kr, wire='packed',
            channel='bitlevel', collective='sharded', mesh=mesh,
            round_idx=n)
        rec = diag.with_allocation(qs, ps, round_idx=n).condensed()
        return (params_ - 0.05 * ghat, jnp.abs(ghat), key_,
                obs_ring.ring_push(ring_, rec)), None

    rec0 = d0.with_allocation(qs, ps, round_idx=jnp.uint32(0)).condensed()

    def carry0():
        return (jnp.zeros((L,)), gbar, jax.random.PRNGKey(11),
                obs_ring.ring_init(rec0, n_scan))

    ns = jnp.arange(n_scan, dtype=jnp.uint32)
    scan_fn = jax.jit(lambda c, xs: jax.lax.scan(round_body, c, xs))
    t0 = time.time()
    scan_fn.lower(carry0(), ns).compile()
    t_compile = time.time() - t0
    reps = 3
    c, _ = scan_fn(carry0(), ns)
    jax.block_until_ready(c)
    t0 = time.time()
    for _ in range(reps):
        c, _ = scan_fn(carry0(), ns)
    jax.block_until_ready(c)
    t_scan = (time.time() - t0) / reps

    body_jit = jax.jit(round_body)
    c, _ = body_jit(carry0(), ns[0])
    jax.block_until_ready(c)
    t0 = time.time()
    for _ in range(reps):
        c = carry0()
        for i in range(n_scan):
            c, _ = body_jit(c, ns[i])
    jax.block_until_ready(c)
    t_eager = (time.time() - t0) / reps

    emit('dist_fused_scan_rounds', 1e6 * t_scan / n_scan,
         f'{n_scan / t_scan:.1f} rounds/s — ONE dispatch per {n_scan}-'
         f'round segment (bitlevel+sharded)')
    emit('dist_fused_eager_rounds', 1e6 * t_eager / n_scan,
         f'{n_scan / t_eager:.1f} rounds/s — per-round dispatch of the '
         f'same body ({t_eager / t_scan:.2f}x the scanned wall-clock)')
    emit('dist_fused_scan_compile', 1e6 * t_compile,
         f'{t_compile:.2f} s trace+compile for the {n_scan}-round scan')


if __name__ == '__main__':
    main()
