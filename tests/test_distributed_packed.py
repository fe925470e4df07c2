"""Sharded packed-domain collective: gathered-vs-sharded parity on the
forced 8-device CPU mesh (the ISSUE-4 acceptance grid).

Contract (see repro.core.transport.__doc__):

* integer partials — sign votes, CRC folds/verdicts, flip counts, and
  the corrupted buffers themselves (the bit channel's counter PRF
  addresses global bit indices) — are bit-exact vs the gathered path;
* the f32 update agrees to the documented ulp contract (per-shard
  sequential accumulation + psum reassociation of the partials);
* ragged K (not divisible by the device count) works via zero-weight
  shard padding.

The tier-1 conftest pins the suite to the true device count, so when
fewer than 8 devices exist this module re-launches itself under pytest
in a subprocess with ``--xla_force_host_platform_device_count=8``; on
the forced mesh the grid below runs in-process.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import transport as TR
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
from repro.wire import format as fmt

ON_MESH = jax.device_count() >= 8
needs_mesh = pytest.mark.skipif(
    not ON_MESH, reason='needs the forced 8-device mesh (the launcher '
                        'test runs this module there)')
_FLAG = '--xla_force_host_platform_device_count=8'


@pytest.mark.slow
@pytest.mark.skipif(ON_MESH, reason='already on the forced mesh')
def test_grid_on_forced_8_device_mesh():
    """Re-run this module's grid in a subprocess that forces 8 host
    devices (XLA device count is fixed at backend init, so the running
    process cannot switch).  Marked slow — a ~2.5 min subprocess run —
    so the fast tier keeps its signal speed; CI covers the grid in the
    bench-smoke job (already on the forced mesh), and tier-1 runs this
    launcher."""
    env = dict(os.environ)
    env['XLA_FLAGS'] = (env.get('XLA_FLAGS', '') + ' ' + _FLAG).strip()
    env['JAX_PLATFORMS'] = 'cpu'
    r = subprocess.run(
        [sys.executable, '-m', 'pytest', '-q', '-p', 'no:cacheprovider',
         os.path.abspath(__file__)],
        env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, (r.stdout[-4000:], r.stderr[-2000:])


# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def mesh():
    return make_mesh((8,), ('data',))


@pytest.fixture(scope='module')
def pod_mesh():
    return make_mesh((2, 4), ('pod', 'data'))


def _payloads(k, n, bits, seed=0):
    rng = np.random.RandomState(seed)
    sign = jnp.asarray(rng.choice([-1, 1], (k, n)), jnp.int8)
    qidx = jnp.asarray(rng.randint(0, 2 ** bits, (k, n)), jnp.int32)
    sw = fmt.pack_bits_ref(fmt.sign_to_bits(sign), 1)
    qw = fmt.pack_bits_ref(qidx, bits)
    scal = dict(
        gmin=jnp.asarray(rng.uniform(0.0, 0.1, k), jnp.float32),
        gmax=jnp.asarray(rng.uniform(0.5, 1.0, k), jnp.float32),
        weight=jnp.asarray(rng.uniform(0.0, 2.0, k), jnp.float32),
        mod_ok=jnp.asarray(rng.rand(k) < 0.7, jnp.float32),
        sign_ok=jnp.asarray(rng.rand(k) < 0.8),
    )
    gbar = jnp.asarray(rng.uniform(0.0, 1.0, n), jnp.float32)
    return sign, sw, qw, gbar, scal


def _grads(k, l, seed):
    g = jax.random.normal(jax.random.PRNGKey(seed), (k, l)) * 0.02
    return jnp.where(g == 0, 1e-4, g)


def _diag_integers_equal(a, b):
    for name in ('sign_ok', 'mod_ok', 'accepted', 'sign_flips',
                 'mod_flips', 'sign_crc_ok', 'mod_crc_ok',
                 'retx_attempts', 'sign_votes'):
        va, vb = getattr(a, name), getattr(b, name)
        if va is None or vb is None:
            assert va is None and vb is None, name
            continue
        assert jnp.array_equal(va, vb), name


# ---------------------------------------------------------------------------
# (a)+(b)+(c): the ops-level grid — ragged K included
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize('bits', [1, 3])
@pytest.mark.parametrize('n', [65, 1000, 4097])      # ragged tails incl.
@pytest.mark.parametrize('k', [5, 8, 16, 33])        # 5, 33: ragged K
def test_sharded_matches_gathered_grid(mesh, k, n, bits):
    sign, sw, qw, gbar, s = _payloads(k, n, bits, seed=k + n + bits)
    acc_s, v_s = ops.spfl_aggregate_packed_sharded(
        sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits, mesh=mesh)
    racc, rv = ref.spfl_packed_aggregate_ref(
        sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits)
    np.testing.assert_allclose(
        np.asarray(acc_s), np.asarray(racc), rtol=0,
        atol=ops.aggregate_atol(s['weight'], s['gmax'], gbar))
    # votes: bit-exact vs the sequential reference — and per-shard vote
    # words lift the capacity to 32 clients/shard, so K=33 still votes
    # (the gathered kernel returns None there)
    assert v_s is not None
    assert jnp.array_equal(v_s, rv)
    acc_g, v_g = ops.spfl_aggregate_packed(
        sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits)
    if v_g is not None:
        assert jnp.array_equal(v_s, v_g)
    np.testing.assert_allclose(
        np.asarray(acc_s), np.asarray(acc_g), rtol=0,
        atol=ops.aggregate_atol(s['weight'], s['gmax'], gbar))


@needs_mesh
def test_sharded_per_client_gbar_and_pod_mesh(pod_mesh):
    k, n, bits = 10, 777, 3                          # ragged on 8 shards
    _, sw, qw, _, s = _payloads(k, n, bits, seed=1)
    gbar_k = jnp.asarray(np.random.RandomState(2).uniform(0, 1, (k, n)),
                         jnp.float32)
    acc_s, _ = ops.spfl_aggregate_packed_sharded(
        sw, qw, gbar_k, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits, mesh=pod_mesh)
    racc, _ = ref.spfl_packed_aggregate_ref(
        sw, qw, gbar_k, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits)
    np.testing.assert_allclose(
        np.asarray(acc_s), np.asarray(racc), rtol=0,
        atol=ops.aggregate_atol(s['weight'], s['gmax'], gbar_k))


@needs_mesh
def test_sharded_fold_and_corrupt_partials(mesh):
    """Partial CRC/erasure state: shard-local corruption and CRC folds
    are bit-identical to the gathered ones (global counter PRF)."""
    rng = np.random.RandomState(7)
    words = jnp.asarray(rng.randint(0, 2 ** 32, (11, 130), np.int64),
                        jnp.uint32)
    ber = jnp.asarray(rng.uniform(0.0, 0.2, 11), jnp.float32)
    key = jax.random.PRNGKey(11)
    got = ops.corrupt_fold_words(key, words, ber, mesh=mesh)
    want = ops.corrupt_fold_words(key, words, ber)
    for g, w in zip(got, want):
        assert jnp.array_equal(g, w)
    assert jnp.array_equal(ops.fold_words(words, mesh=mesh),
                           ops.fold_words(words))


# ---------------------------------------------------------------------------
# transport level: flat + tree, clean + bitlevel channels
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize('channel,n_retx', [('bernoulli', 0),
                                            ('bitlevel', 0),
                                            ('bitlevel', 1)])
def test_flat_transport_sharded_matches_gathered(mesh, channel, n_retx):
    k, l, bits = 6, 2000, 3                          # ragged on 8 shards
    grads = _grads(k, l, seed=5)
    gbar = jnp.abs(grads[0])
    q = jnp.linspace(0.3, 0.9, k)
    p = jnp.linspace(0.4, 0.95, k)
    key = jax.random.PRNGKey(6)
    gh_g, d_g = TR.spfl_aggregate(grads, gbar, q, p, bits, 64, key,
                                  n_retx=n_retx, wire='packed',
                                  channel=channel)
    gh_s, d_s = TR.spfl_aggregate(grads, gbar, q, p, bits, 64, key,
                                  n_retx=n_retx, wire='packed',
                                  channel=channel, collective='sharded',
                                  mesh=mesh)
    _diag_integers_equal(d_g, d_s)
    assert float(d_g.payload_bits) == float(d_s.payload_bits)
    w = TR._inverse_prob(d_g.sign_ok, 1.0 - (1.0 - q) ** (n_retx + 1))
    gmax = jnp.max(jnp.abs(grads), axis=1)
    np.testing.assert_allclose(
        np.asarray(gh_g), np.asarray(gh_s), rtol=0,
        atol=ops.aggregate_atol(w, gmax, gbar) / k)


@needs_mesh
def test_flat_sharded_under_jit_with_sharded_inputs(mesh):
    from repro.launch import shardings as SH
    k, l, bits = 16, 4096, 3
    grads = jax.device_put(_grads(k, l, seed=9), SH.client_sharding(mesh))
    gbar = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (l,)))
    q = p = jnp.full((k,), 0.8)
    agg_s = jax.jit(lambda kk: TR.spfl_aggregate(
        grads, gbar, q, p, bits, 64, kk, wire='packed',
        collective='sharded', mesh=mesh))
    agg_g = jax.jit(lambda kk: TR.spfl_aggregate(
        grads, gbar, q, p, bits, 64, kk, wire='packed'))
    gh_s, d_s = agg_s(jax.random.PRNGKey(2))
    gh_g, d_g = agg_g(jax.random.PRNGKey(2))
    _diag_integers_equal(d_g, d_s)
    w = TR._inverse_prob(d_g.sign_ok, q)
    gmax = jnp.max(jnp.abs(grads), axis=1)
    np.testing.assert_allclose(
        np.asarray(gh_g), np.asarray(gh_s), rtol=0,
        atol=ops.aggregate_atol(w, gmax, gbar) / k)


@needs_mesh
@pytest.mark.parametrize('channel', ['bernoulli', 'bitlevel'])
def test_tree_transport_sharded_matches_gathered(mesh, channel):
    k = 12                                           # ragged on 8 shards
    grads = _grads(k, 300, seed=13)
    tree = {'a': grads[:, :64].reshape(k, 8, 8), 'b': grads[:, 64:]}
    gbar = jnp.abs(grads[0])
    gbar_tree = {'a': gbar[:64].reshape(8, 8), 'b': gbar[64:]}
    q = jnp.full((k,), 0.7)
    p = jnp.full((k,), 0.6)
    fl = FLConfig(wire='packed', channel=channel)
    key = jax.random.PRNGKey(14)
    out_g, _, d_g = TR.spfl_aggregate_tree(tree, gbar_tree, q, p, fl, key)
    out_s, _, d_s = TR.spfl_aggregate_tree(tree, gbar_tree, q, p, fl, key,
                                           collective='sharded', mesh=mesh)
    _diag_integers_equal(d_g, d_s)
    assert float(d_g.payload_bits) == float(d_s.payload_bits)
    w = TR._inverse_prob(d_g.sign_ok, q)
    gmax = jnp.max(jnp.abs(grads), axis=1)
    for leaf in out_g:
        np.testing.assert_allclose(
            np.asarray(out_g[leaf]), np.asarray(out_s[leaf]), rtol=0,
            atol=ops.aggregate_atol(w, gmax, gbar) / k)


@needs_mesh
def test_error_free_sharded_matches_gathered(mesh):
    k, l = 8, 1500
    grads = _grads(k, l, seed=21)
    fl = FLConfig(wire='packed')
    key = jax.random.PRNGKey(22)
    gh_g, d_g = TR.error_free_aggregate(grads, fl, key)
    gh_s, d_s = TR.error_free_aggregate(grads, fl, key,
                                        collective='sharded', mesh=mesh)
    _diag_integers_equal(d_g, d_s)
    gmax = jnp.max(jnp.abs(grads), axis=1)
    np.testing.assert_allclose(
        np.asarray(gh_g), np.asarray(gh_s), rtol=0,
        atol=ops.aggregate_atol(jnp.ones(k), gmax, jnp.zeros(1)) / k)
    tree = {'a': grads[:, :512], 'b': grads[:, 512:]}
    t_g, _, _ = TR.error_free_aggregate_tree(tree, fl, key)
    t_s, _, _ = TR.error_free_aggregate_tree(tree, fl, key,
                                             collective='sharded',
                                             mesh=mesh)
    for leaf in t_g:
        np.testing.assert_allclose(
            np.asarray(t_g[leaf]), np.asarray(t_s[leaf]), rtol=0,
            atol=ops.aggregate_atol(jnp.ones(k), gmax, jnp.zeros(1)) / k)


@needs_mesh
def test_fl_train_step_sharded_collective(mesh):
    """End-to-end distributed.py wiring: one FL train step whose uplink
    reduce is the sharded packed collective."""
    from repro.configs.registry import get_arch
    from repro.data import synth_tokens
    from repro.models import transformer as tf
    from repro.training import distributed as D
    cfg = get_arch('smollm-135m').reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    K, b, T = 4, 1, 32                               # ragged: 4 < 8 devices
    toks = synth_tokens(K * b, T, cfg.vocab_size, 0).reshape(K, b, T)
    batch = {'tokens': jnp.asarray(toks)}
    gbar = D.init_gbar(params)
    q = p = jnp.ones((K,))
    key = jax.random.PRNGKey(3)
    fl_s = FLConfig(n_devices=K, wire='packed', collective='sharded')
    step_s = jax.jit(D.make_fl_train_step(cfg, fl_s, 'spfl', mesh=mesh))
    p_s, _, m_s = step_s(params, batch, gbar, q, p, key)
    fl_g = FLConfig(n_devices=K, wire='packed')
    step_g = jax.jit(D.make_fl_train_step(cfg, fl_g, 'spfl'))
    p_g, _, m_g = step_g(params, batch, gbar, q, p, key)
    assert np.isfinite(float(m_s['loss']))
    assert float(m_s['loss']) == float(m_g['loss'])  # same grads/draws
    np.testing.assert_allclose(float(m_s['payload_bits']),
                               float(m_g['payload_bits']))
    for leaf_s, leaf_g in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_g)):
        np.testing.assert_allclose(np.asarray(leaf_s), np.asarray(leaf_g),
                                   atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# knob validation (device-count independent)
# ---------------------------------------------------------------------------

def test_sharded_requires_packed_wire_and_mesh():
    grads = _grads(4, 100, seed=31)
    gbar = jnp.abs(grads[0])
    ones = jnp.ones((4,))
    with pytest.raises(ValueError, match="wire='packed'"):
        TR.spfl_aggregate(grads, gbar, ones, ones, 3, 64,
                          jax.random.PRNGKey(0), wire='analytic',
                          collective='sharded')
    with pytest.raises(ValueError, match='mesh'):
        TR.spfl_aggregate(grads, gbar, ones, ones, 3, 64,
                          jax.random.PRNGKey(0), wire='packed',
                          collective='sharded')
    # jax.make_mesh's default Explicit axes cannot carry the GSPMD program
    with pytest.raises(ValueError, match='Auto-typed'):
        TR.spfl_aggregate(grads, gbar, ones, ones, 3, 64,
                          jax.random.PRNGKey(0), wire='packed',
                          collective='sharded',
                          mesh=jax.make_mesh((1,), ('data',)))
    with pytest.raises(ValueError, match='mesh'):
        from repro.configs.registry import get_arch
        from repro.training import distributed as D
        D.make_fl_train_step(get_arch('smollm-135m').reduced(),
                             FLConfig(wire='packed', collective='sharded'),
                             'spfl')
