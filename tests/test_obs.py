"""Zero-sync round telemetry (repro.obs): the transfer-guard proof, the
JSONL schema round-trip across the wire/channel/collective grid, the ring
buffer, the metrics registry, the run manifest, and the report_history
exit-0 contract.

The headline test is ``test_zero_device_to_host_transfers``: with
``jax.transfer_guard_device_to_host('disallow')`` armed, a jitted
transport round plus ring push must run WITHOUT any device->host
transfer — the contract that lets telemetry ride inside a fully-fused
round loop.  Only ``flush`` (outside the guard) syncs.
"""
import dataclasses
import json
import math
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import transport as TR
from repro.launch.mesh import make_host_mesh
from repro.obs import (
    SCALAR_KEYS, JsonlSink, MetricsRegistry, ReservoirHistogram,
    RoundTelemetry, config_hash, read_jsonl, ring_init, ring_push,
    round_scalars, run_manifest, to_row,
)
from repro.obs import ringbuf as obs_ring
from repro.obs.trace import (
    SPAN_CAPACITY, SPANS, StageTrace, compile_counters, span,
)
from repro.training.fl_loop import FLHistory

K, L = 4, 256
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), '..'))


def _mk_rec(i=0, votes=False, crc=False, adversarial=False):
    r = RoundTelemetry(
        sign_ok=jnp.ones((K,), bool),
        mod_ok=jnp.asarray([True, False, True, True]),
        accepted=jnp.ones((K,), bool),
        payload_bits=jnp.float32(1000.0 + i),
        retransmissions=jnp.float32(i),
    )
    if votes:
        r = r._replace(sign_votes=jnp.full((L,), K, jnp.int32))
    if crc:
        r = r._replace(sign_crc_ok=jnp.ones((K,), bool),
                       mod_crc_ok=jnp.zeros((K,), bool))
    if adversarial:
        r = r._replace(active=jnp.asarray([True, True, False, True]),
                       suspect=jnp.asarray([False, True, False, False]),
                       suspicion=jnp.asarray([0.1, 9.0, 0.0, 0.2],
                                             jnp.float32))
    return r.with_allocation(jnp.full((K,), 0.9), jnp.full((K,), 0.6),
                             round_idx=jnp.uint32(i))


# ---------------------------------------------------------------------------
# the zero-sync contract
# ---------------------------------------------------------------------------

def test_zero_device_to_host_transfers():
    """Non-flush rounds do ZERO device->host transfers: jitted transport
    + ring push run under a disallow transfer guard."""
    fl = FLConfig(n_devices=K)
    key = jax.random.PRNGKey(0)
    grads = jax.random.normal(key, (K, L)) * 0.01
    gbar = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (L,)))
    q = jnp.full((K,), 0.9)
    p = jnp.full((K,), 0.6)

    @jax.jit
    def round_step(ring, kk, i):
        ghat, diag = TR.spfl_aggregate(
            grads, gbar, q, p, fl.quant_bits, fl.b0_bits, kk,
            wire='packed', round_idx=i)
        rec = diag.with_allocation(q, p, round_idx=i).condensed()
        return ghat, obs_ring.ring_push(ring, rec)

    keys = jax.random.split(jax.random.fold_in(key, 2), 8)
    idxs = jnp.arange(8, dtype=jnp.uint32)
    # warm up: compilation itself may transfer (constants, donation setup)
    _, d0 = jax.jit(lambda kk: TR.spfl_aggregate(
        grads, gbar, q, p, fl.quant_bits, fl.b0_bits, kk,
        wire='packed', round_idx=jnp.uint32(0)))(keys[0])
    ring = ring_init(
        d0.with_allocation(q, p, round_idx=jnp.uint32(0)).condensed(), 8)
    ghat, ring = round_step(ring, keys[0], idxs[0])
    jax.block_until_ready(ghat)

    with jax.transfer_guard_device_to_host('disallow'):
        for i in range(1, 6):
            ghat, ring = round_step(ring, keys[i], idxs[i])
        jax.block_until_ready(ghat)

    rows, ring = obs_ring.flush(ring)          # the ONE sync, outside
    assert len(rows) == 6
    assert [int(np.asarray(r.round_idx)) for r in rows] == [0, 1, 2, 3, 4, 5]


def test_flush_syncs_and_resets():
    rec = _mk_rec()
    ring = ring_init(rec, 4)
    for i in range(3):
        ring = ring_push(ring, _mk_rec(i))
    rows, ring2 = obs_ring.flush(ring)
    assert len(rows) == 3
    assert [float(r.payload_bits) for r in rows] == [1000.0, 1001.0, 1002.0]
    assert int(ring2.idx) == 0                 # reset, device buf reused
    rows2, _ = obs_ring.flush(ring2)
    assert rows2 == []


def test_ring_wraps_oldest_first():
    ring = ring_init(_mk_rec(), 3)
    for i in range(5):                         # 5 pushes into capacity 3
        ring = ring_push(ring, _mk_rec(i))
    rows, _ = obs_ring.flush(ring)
    assert [int(np.asarray(r.round_idx)) for r in rows] == [2, 3, 4]


# ---------------------------------------------------------------------------
# serializers: one schema, traceable and host-side
# ---------------------------------------------------------------------------

def test_round_scalars_keys_match_flhistory():
    """The traceable scalar summary is keyed exactly like the matching
    FLHistory per-round lists — the shared-serializer contract that
    retired the hand-rolled dict in training/distributed.py."""
    hist_keys = set(FLHistory().as_dict())
    assert set(SCALAR_KEYS) <= hist_keys
    s = jax.jit(round_scalars)(_mk_rec(votes=True))
    assert set(s) == set(SCALAR_KEYS)


def test_to_row_matches_round_scalars():
    rec = _mk_rec(votes=True, crc=True)
    row = to_row(rec)
    s = round_scalars(rec)
    for k in SCALAR_KEYS:
        # nan_ok: unmeasured scalars (e.g. alloc_iters off a solving
        # path) are NaN in BOTH serializers by the schema contract
        assert row[k] == pytest.approx(float(s[k]), rel=1e-6,
                                       nan_ok=True), k
    assert row['round'] == 0
    # empirical-vs-calibrated erasure pair (bit channel)
    assert row['sign_erasure_emp'] == 0.0
    assert row['sign_erasure_cal'] == pytest.approx(0.1, rel=1e-5)
    assert row['mod_erasure_emp'] == 1.0


def test_adversarial_fields_in_both_serializers():
    """active/suspect/suspicion flow through both serializers: NaN
    scalars when unmeasured (seed paths share a treedef), exact
    fractions + (K,) vectors when the adversarial path measured them;
    condensed() passes the O(K) fields through untouched."""
    plain = _mk_rec()
    s = round_scalars(plain)
    assert math.isnan(float(s['participation_frac']))
    assert math.isnan(float(s['suspect_frac']))
    assert to_row(plain)['suspect'] is None

    rec = _mk_rec(votes=True, adversarial=True)
    s = round_scalars(rec)
    assert float(s['participation_frac']) == pytest.approx(0.75)
    assert float(s['suspect_frac']) == pytest.approx(0.25)
    row = to_row(rec)
    assert row['participation_frac'] == pytest.approx(0.75)
    assert row['active'] == [True, True, False, True]
    assert row['suspect'] == [False, True, False, False]
    assert row['suspicion'] == pytest.approx([0.1, 9.0, 0.0, 0.2])
    cond = rec.condensed()
    assert cond.sign_votes is None          # O(l) vector reduced away
    assert np.array_equal(np.asarray(cond.suspicion),
                          np.asarray(rec.suspicion))
    assert np.array_equal(np.asarray(cond.active), np.asarray(rec.active))


def test_zero_transfers_with_screening_and_dropout():
    """The transfer-guard contract extends to the adversarial config:
    attack + packed-domain screen + dropout gating all run device-side
    inside the jitted round, telemetry included."""
    from repro import adversary as adv
    fl = FLConfig(n_devices=K)
    key = jax.random.PRNGKey(0)
    common = jax.random.normal(key, (L,))
    grads = (common[None, :]
             + 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                       (K, L))) * 0.01
    gbar = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (L,)))
    q = jnp.full((K,), 0.9)
    p = jnp.full((K,), 0.6)
    byz = adv.byzantine_mask(0, K, 0.25)

    @jax.jit
    def round_step(ring, kk, i):
        active = adv.bernoulli_active(
            jax.random.fold_in(kk, adv.STRAGGLER_FOLD), K, 0.2)
        ghat, diag = TR.spfl_aggregate(
            grads, gbar, q, p, fl.quant_bits, fl.b0_bits, kk,
            wire='packed', channel='bitlevel', round_idx=i,
            attack='signflip', byz_mask=byz, active=active,
            screen=True, min_participation=0.25)
        rec = diag.with_allocation(q, p, round_idx=i).condensed()
        return ghat, obs_ring.ring_push(ring, rec)

    keys = jax.random.split(jax.random.fold_in(key, 3), 6)
    idxs = jnp.arange(6, dtype=jnp.uint32)
    # warm-up round builds the ring prototype
    _, diag = jax.jit(lambda kk, i: TR.spfl_aggregate(
        grads, gbar, q, p, fl.quant_bits, fl.b0_bits, kk,
        wire='packed', channel='bitlevel', round_idx=i,
        attack='signflip', byz_mask=byz,
        active=adv.bernoulli_active(
            jax.random.fold_in(kk, adv.STRAGGLER_FOLD), K, 0.2),
        screen=True, min_participation=0.25))(keys[0], idxs[0])
    ring = ring_init(
        diag.with_allocation(q, p, round_idx=idxs[0]).condensed(), 6)
    ghat, ring = round_step(ring, keys[0], idxs[0])
    jax.block_until_ready(ghat)
    with jax.transfer_guard_device_to_host('disallow'):
        for i in range(1, 5):
            ghat, ring = round_step(ring, keys[i], idxs[i])
        jax.block_until_ready(ghat)
    rows, _ = obs_ring.flush(ring)
    assert len(rows) == 5
    for r in rows:
        assert r.active.shape == (K,) and r.suspicion.shape == (K,)
        assert to_row(r)['suspect_frac'] >= 0.0


@pytest.mark.skipif(jax.device_count() < 1, reason='needs a device')
def test_zero_transfers_screening_sharded():
    """Sharded collective + screening under the device->host guard —
    the global-view vote/z-score stays a GSPMD computation."""
    from repro import adversary as adv
    mesh = make_host_mesh()
    key = jax.random.PRNGKey(1)
    common = jax.random.normal(key, (L,))
    grads = (common[None, :]
             + 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                       (K, L))) * 0.01
    gbar = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (L,)))
    q = jnp.full((K,), 0.9)
    p = jnp.full((K,), 0.6)
    byz = adv.byzantine_mask(0, K, 0.25)
    fl = FLConfig(n_devices=K)

    agg = jax.jit(lambda kk, i: TR.spfl_aggregate(
        grads, gbar, q, p, fl.quant_bits, fl.b0_bits, kk,
        wire='packed', channel='bitlevel', collective='sharded',
        mesh=mesh, round_idx=i, attack='signflip', byz_mask=byz,
        screen=True))
    g0, d0 = agg(jax.random.fold_in(key, 3), jnp.uint32(0))
    jax.block_until_ready(g0)
    with jax.transfer_guard_device_to_host('disallow'):
        g1, d1 = agg(jax.random.fold_in(key, 4), jnp.uint32(1))
        jax.block_until_ready((g1, d1.suspect))
    assert d1.suspect.shape == (K,)
    assert bool(np.all(np.isfinite(np.asarray(g1))))


def test_condensed_preserves_agreement():
    rec = _mk_rec(votes=True)
    cond = rec.condensed()
    assert cond.sign_votes is None and cond.agreement is not None
    assert to_row(cond)['sign_agreement'] == pytest.approx(
        to_row(rec)['sign_agreement'])
    assert float(round_scalars(cond)['sign_agreement']) == pytest.approx(
        float(round_scalars(rec)['sign_agreement']))


def test_retired_diagnostics_attribute_surface():
    """RoundTelemetry keeps the exact attribute surface of the retired
    TransportDiagnostics (the transports construct it positionally, the
    packed-wire tests getattr these names)."""
    for name in ('sign_ok', 'mod_ok', 'accepted', 'payload_bits',
                 'retransmissions', 'sign_flips', 'mod_flips',
                 'sign_crc_ok', 'mod_crc_ok', 'retx_attempts',
                 'sign_votes'):
        assert hasattr(_mk_rec(), name), name
    assert not hasattr(TR, 'TransportDiagnostics')


# ---------------------------------------------------------------------------
# JSONL round-trip across the wire x channel x collective grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('wire,channel,collective', [
    ('analytic', 'bernoulli', 'gather'),
    ('packed', 'bernoulli', 'gather'),
    ('packed', 'bitlevel', 'gather'),
    ('packed', 'bitlevel', 'sharded'),
])
def test_jsonl_round_trip(tmp_path, wire, channel, collective):
    fl = dataclasses.replace(FLConfig(n_devices=K), wire=wire,
                             channel=channel, collective=collective)
    mesh = None
    if collective == 'sharded':
        mesh = make_host_mesh()
    key = jax.random.PRNGKey(3)
    grads = jax.random.normal(key, (K, L)) * 0.01
    gbar = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (L,)))
    q = jnp.full((K,), 0.9)
    p = jnp.full((K,), 0.6)

    agg = jax.jit(lambda kk, i: TR.spfl_aggregate(
        grads, gbar, q, p, fl.quant_bits, fl.b0_bits, kk, wire=wire,
        channel=channel, round_idx=i, collective=collective, mesh=mesh))
    path = tmp_path / f'{wire}_{channel}_{collective}.jsonl'
    man_in = run_manifest(fl, mesh=mesh, extra={'driver': 'test'})
    with JsonlSink(str(path), man_in) as sink:
        for i in range(3):
            _, diag = agg(jax.random.fold_in(key, 10 + i), jnp.uint32(i))
            sink.write_round(to_row(
                diag.with_allocation(q, p, round_idx=jnp.uint32(i))))

    man, rows = read_jsonl(str(path))
    # manifest completeness
    for k in ('date', 'git_sha', 'config_hash', 'config', 'platform',
              'jax', 'xla_flags', 'env', 'mesh'):
        assert k in man, k
    assert man['config']['wire'] == wire
    assert man['config_hash'] == config_hash(fl)
    assert (man['mesh'] is None) == (mesh is None)
    # rows: schema + strict JSON (every line parses, NaN became null)
    assert [r['round'] for r in rows] == [0, 1, 2]
    for r in rows:
        for k in SCALAR_KEYS:
            assert k in r, k
        assert len(r['sign_ok']) == K
        if channel == 'bitlevel':
            assert 'sign_erasure_emp' in r and 'sign_erasure_cal' in r
        else:
            assert r.get('sign_crc_ok') is None
    for line in path.read_text().splitlines():
        json.loads(line)                       # strict: no NaN literals


def test_jsonl_round_trip_adversarial_fields(tmp_path):
    fl = dataclasses.replace(FLConfig(n_devices=K), screen=True,
                             attack='signflip', dropout_rate=0.2)
    path = tmp_path / 'adv.jsonl'
    with JsonlSink(str(path), run_manifest(fl)) as sink:
        sink.write_round(to_row(_mk_rec(0, adversarial=True)))
    man, rows = read_jsonl(str(path))
    assert man['config']['screen'] is True
    assert man['config']['attack'] == 'signflip'
    r = rows[0]
    assert r['participation_frac'] == pytest.approx(0.75)
    assert r['suspect_frac'] == pytest.approx(0.25)
    assert r['active'] == [True, True, False, True]
    assert r['suspicion'] == pytest.approx([0.1, 9.0, 0.0, 0.2])


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_routes_rows():
    reg = MetricsRegistry()
    for i in range(4):
        reg.observe_round(to_row(_mk_rec(i, votes=True, crc=True)))
    reg.observe_alloc(host_solver_calls=2, outer_residual=0.5)
    snap = reg.snapshot()
    assert set(snap) == {'transport', 'bitchannel', 'allocation'}
    tr_ = snap['transport']
    assert tr_['payload_bits']['kind'] == 'counter'
    assert tr_['payload_bits']['value'] == pytest.approx(
        sum(1000.0 + i for i in range(4)))
    assert tr_['retransmissions']['value'] == pytest.approx(6.0)
    assert snap['allocation']['host_solver_calls']['value'] == 2.0
    assert snap['bitchannel']['sign_erasure_emp']['value'] == 0.0
    assert snap['allocation']['outer_residual_hist']['count'] == 1


def test_reservoir_histogram_deterministic():
    h1 = ReservoirHistogram(size=32, seed=7)
    h2 = ReservoirHistogram(size=32, seed=7)
    for i in range(200):
        h1.observe(float(i))
        h2.observe(float(i))
    assert h1.snapshot() == h2.snapshot()
    s = h1.snapshot()
    assert s['count'] == 200 and s['p50'] <= s['p90'] <= s['p99']


# ---------------------------------------------------------------------------
# run manifest / launch.env
# ---------------------------------------------------------------------------

def test_manifest_records_env_state(monkeypatch, tmp_path):
    from repro.launch import env as launch_env
    # an outside cache directory leaves jax's in-process cache untouched
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    launch_env.configure()
    man = run_manifest(FLConfig())
    assert man['env']['configured'] is True
    assert man['env']['device_count'] == jax.device_count()
    assert man['jax']['backend'] == jax.default_backend()
    assert len(man['config_hash']) == 16
    # hash keys on config content, not object identity
    assert config_hash(FLConfig()) == man['config_hash']
    assert config_hash(FLConfig(seed=1)) != man['config_hash']


# ---------------------------------------------------------------------------
# report_history: informational tool, always exit 0
# ---------------------------------------------------------------------------

def _run_report(cwd):
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, 'benchmarks',
                                      'report_history.py')],
        capture_output=True, text=True, cwd=cwd)


def test_report_history_exit0_on_repo():
    r = _run_report(_ROOT)
    assert r.returncode == 0, r.stderr


def test_report_history_single_and_empty_entries(tmp_path):
    from importlib import util
    spec = util.spec_from_file_location(
        'report_history', os.path.join(_ROOT, 'benchmarks',
                                       'report_history.py'))
    rh = util.module_from_spec(spec)
    spec.loader.exec_module(rh)
    single = tmp_path / 'BENCH_one.json'
    single.write_text(json.dumps(
        {'suite': 'one', 'history': [{'sha': 'abc', 'date': 'd',
                                      'rows': []}]}))
    empty = tmp_path / 'BENCH_none.json'
    empty.write_text(json.dumps({'suite': 'none', 'history': []}))
    broken = tmp_path / 'BENCH_broken.json'
    broken.write_text('{not json')
    malformed = tmp_path / 'BENCH_malformed.json'
    malformed.write_text(json.dumps({'suite': 'mal', 'history': [
        {'sha': 'a', 'date': 'd', 'rows': [{'name': 'x',
                                            'us_per_call': 1.0}]},
        {'sha': 'b', 'date': 'e', 'rows': [{'no_name': True},
                                           {'name': 'x',
                                            'us_per_call': 2.0}]},
    ]}))
    # none of these raise; each prints a clear line instead
    for p in (single, empty, broken, malformed):
        rh.report(str(p))


# ---------------------------------------------------------------------------
# solver step counter, stage scopes, host spans, compile counters
# ---------------------------------------------------------------------------

def test_alloc_steps_survives_ring_flush_and_serializers():
    """``alloc_steps`` rides the record like ``alloc_iters``: ring push,
    flush, ``to_row``/``round_scalars`` and the allocation channel."""
    rec = _mk_rec().with_allocation(
        jnp.full((K,), 0.9), jnp.full((K,), 0.6), iters=jnp.int32(2),
        steps=jnp.int32(6048))
    ring = ring_push(ring_init(rec, 2), rec)
    rows, _ = obs_ring.flush(ring)
    row = to_row(rows[0])
    assert row['alloc_steps'] == 6048.0
    assert float(round_scalars(rows[0])['alloc_steps']) == 6048.0
    assert math.isnan(to_row(_mk_rec())['alloc_steps'])
    reg = MetricsRegistry()
    reg.observe_round(row)
    al = reg.snapshot()['allocation']
    assert al['alloc_steps']['value'] == 6048.0
    assert al['alloc_steps_hist']['count'] == 1


def test_alloc_dual_rounds_survives_ring_flush_and_serializers():
    """``alloc_dual_rounds`` rides the record like ``alloc_steps``: ring
    push, flush, ``to_row``/``round_scalars`` and the allocation
    channel; NaN on a record that solved nothing."""
    rec = _mk_rec().with_allocation(
        jnp.full((K,), 0.9), jnp.full((K,), 0.6), iters=jnp.int32(2),
        steps=jnp.int32(1344), dual_rounds=jnp.int32(24))
    ring = ring_push(ring_init(rec, 2), rec)
    rows, _ = obs_ring.flush(ring)
    row = to_row(rows[0])
    assert row['alloc_dual_rounds'] == 24.0
    assert float(round_scalars(rows[0])['alloc_dual_rounds']) == 24.0
    assert math.isnan(to_row(_mk_rec())['alloc_dual_rounds'])
    reg = MetricsRegistry()
    reg.observe_round(row)
    al = reg.snapshot()['allocation']
    assert al['alloc_dual_rounds']['value'] == 24.0
    assert al['alloc_dual_rounds_hist']['count'] == 1


def _hlo_sources(hlo: str):
    """[(op_name, innermost repro/ file)] of the HLO instructions whose
    metadata names a stack frame (the compiled module's frame tables)."""
    tables, cur = {}, None
    for line in hlo.splitlines():
        if line in ('FileNames', 'FileLocations', 'StackFrames'):
            cur = tables.setdefault(line, {})
            continue
        m = re.match(r'^(\d+) (.*)$', line) if cur is not None else None
        if not m:
            cur = None
            continue
        body = m.group(2)
        cur[int(m.group(1))] = (body.strip('"') if body.startswith('"')
                                else {k: int(v) for k, v in
                                      re.findall(r'(\w+)=(\d+)', body)})

    def source(frame):
        seen = set()
        while frame in tables['StackFrames'] and frame not in seen:
            seen.add(frame)
            fr = tables['StackFrames'][frame]
            loc = tables['FileLocations'][fr['file_location_id']]
            path = tables['FileNames'][loc['file_name_id']]
            if 'repro/' in path:
                return path
            frame = fr['parent_frame_id']
        return ''

    return [(m.group(1), source(int(m.group(2)))) for m in re.finditer(
        r'op_name="([^"]*)"[^}]*stack_frame_id=(\d+)', hlo)]


def test_stage_scopes_name_the_compiled_fused_segment():
    """Every stage of ``obs.trace.STAGES`` names ops of a compiled
    2-round fused segment (packed wire, bit channel, screening, AR(1)
    fading, in-trace solve); ``psum`` exists only on the sharded
    collective, compiled here on the host mesh.  Every op whose source is
    the model lies under ``obs/grad``."""
    from repro.obs.trace import STAGES
    from repro.training.fl_loop import FLSimulator
    fl = FLConfig(n_devices=K, allocator='barrier', seed=0,
                  allocation_backend='jax', round_fusion='scan',
                  wire='packed', channel='bitlevel', screen=True,
                  allocation_cadence='per_round', telemetry_flush_every=2)
    rng = np.random.RandomState(0)
    xs = rng.rand(K, 8, 32, 32, 3).astype(np.float32)
    ys = rng.randint(0, 10, (K, 8)).astype(np.int32)
    sim = FLSimulator(fl, xs, ys, xs[0], ys[0])
    body = sim._fused_round_body()
    hlo = jax.jit(lambda c, ns: jax.lax.scan(body, c, ns)).lower(
        sim._fused_init_carry(2), jnp.arange(2, dtype=jnp.uint32)
    ).compile().as_text()
    grads = jnp.ones((K, L)) * 0.01
    q = jnp.full((K,), 0.9)
    hlo_psum = jax.jit(lambda kk: TR.spfl_aggregate(
        grads, jnp.ones((L,)), q, q, 3, 32, kk, wire='packed',
        collective='sharded', mesh=make_host_mesh())[0]).lower(
            jax.random.PRNGKey(0)).compile().as_text()

    def scopes(text):
        return {seg[len('obs/'):].split('/')[0]
                for name in re.findall(r'op_name="([^"]*)"', text)
                for seg in re.findall(r'obs/[\w]+', name)}

    found = scopes(hlo)
    assert set(STAGES) - {'psum'} <= found, set(STAGES) - found
    assert 'psum' in scopes(hlo_psum)
    assert found | scopes(hlo_psum) <= set(STAGES)
    model_ops = [name for name, src in _hlo_sources(hlo)
                 if 'repro/models/' in src and name.startswith('jit(')]
    assert model_ops
    assert all('/obs/grad/' in name for name in model_ops), [
        n for n in model_ops if '/obs/grad/' not in n][:3]


def test_host_spans_nest_and_are_bounded():
    tracer = StageTrace()
    before = time.time_ns()
    with tracer.span('outer'):
        with tracer.span('inner'):
            pass
    after = time.time_ns()
    inner, outer = tracer.spans()
    assert (inner.name, outer.name) == ('inner', 'outer')
    assert inner.parent == outer.id and outer.parent is None
    assert before <= outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= outer.end_ns <= after
    mark = tracer.mark()
    for i in range(SPAN_CAPACITY + 1):
        with tracer.span('s'):
            pass
    kept = tracer.spans()
    assert len(kept) == SPAN_CAPACITY
    assert kept[0].id == mark + 1 and kept[-1].id == mark + SPAN_CAPACITY
    assert set(tracer.summary()) == {'s'}
    assert tracer.summary(since=kept[-1].id)['s']['count'] == 1


def test_flush_records_its_spans():
    ring = ring_push(ring_init(_mk_rec(), 2), _mk_rec())
    obs_ring.flush(ring)
    *children, parent = SPANS.spans()[-4:]
    assert parent.name == 'flush'
    assert [c.name for c in children] == ['flush.get', 'flush.unstack',
                                          'flush.reset']
    assert all(c.parent == parent.id for c in children)


def test_compile_counter_counts_a_fresh_jit():
    x = jnp.arange(3.0)
    before = compile_counters()
    jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    after = compile_counters()
    for kind in ('trace', 'lower', 'backend_compile'):
        assert after[kind]['count'] == before[kind]['count'] + 1, kind
        assert after[kind]['seconds'] > before[kind]['seconds'], kind
    assert after['total_s'] > before['total_s']


def test_host_spans_leave_compiled_hlo_unchanged():
    def f(v):
        return jnp.sin(v) * 2.0

    x = jnp.arange(4.0)
    plain = jax.jit(f).lower(x).compile().as_text()
    with span('outer'):
        inside = jax.jit(f).lower(x).compile().as_text()
    assert inside == plain
