"""Packed-domain hot path (decode-once aggregation + fused corruption):
parity against the retained unpack-per-client / materialized references.

Exactness contract (see repro.core.transport.__doc__):

* integer domain — decoded signs/knobs, sign votes, flip masks, folds,
  flip counts — is bit-exact everywhere;
* the f32 reconstruction of the decode-once kernel agrees with the jnp
  references to within a couple of ulp (the compiler FMA-contracts the
  kernel's fused mul+add chains), pinned by ``ops.aggregate_atol``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import bitchannel as BC
from repro.core import transport as TR
from repro.kernels import ops, ref
from repro.wire import corrupt as WC
from repro.wire import format as fmt
from repro.wire import packets

FL = FLConfig()


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
    return sign, qidx, sw, qw, gbar, scal


# ---------------------------------------------------------------------------
# decode-once aggregation vs the seed unpack-per-client reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('bits', [1, 3, 8])
@pytest.mark.parametrize('n', [37, 65, 1000, 4097])   # ragged tails incl.
@pytest.mark.parametrize('k', [1, 2, 6])
@pytest.mark.parametrize('use_kernel', [True, False])
def test_decode_once_matches_reference_grid(n, bits, k, use_kernel):
    """Both dispatches — the Pallas kernel (interpret) and its live jnp
    twin — against the seed unpack-per-client reference."""
    sign, qidx, sw, qw, gbar, s = _payloads(k, n, bits, seed=n + bits + k)
    acc, votes = ops.spfl_aggregate_packed(
        sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits, interpret=True, use_kernel=use_kernel)
    racc, rvotes = ref.spfl_packed_aggregate_ref(
        sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits)
    np.testing.assert_allclose(
        np.asarray(acc), np.asarray(racc), rtol=0,
        atol=ops.aggregate_atol(s['weight'], s['gmax'], gbar))
    assert jnp.array_equal(votes, rvotes)            # integers: bit-exact
    # votes are the per-coordinate +1 count among accepted clients
    expect = jnp.sum((sign > 0) & s['sign_ok'][:, None], axis=0)
    assert jnp.array_equal(votes, expect.astype(jnp.int32))


def test_decode_once_per_client_gbar():
    k, n, bits = 4, 777, 3
    sign, qidx, sw, qw, _, s = _payloads(k, n, bits, seed=1)
    gbar_k = jnp.asarray(np.random.RandomState(2).uniform(0, 1, (k, n)),
                         jnp.float32)
    acc, _ = ops.spfl_aggregate_packed(
        sw, qw, gbar_k, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits, interpret=True, use_kernel=True)
    racc, _ = ref.spfl_packed_aggregate_ref(
        sw, qw, gbar_k, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits)
    np.testing.assert_allclose(
        np.asarray(acc), np.asarray(racc), rtol=0,
        atol=ops.aggregate_atol(s['weight'], s['gmax'], gbar_k))


def test_decode_once_votes_capacity():
    """Votes ride a 32-bit transposed word: present up to K = 32 clients,
    None beyond."""
    for k, present in ((32, True), (33, False)):
        sign, qidx, sw, qw, gbar, s = _payloads(k, 200, 3, seed=k)
        acc, votes = ops.spfl_aggregate_packed(
            sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
            s['sign_ok'], 200, 3, interpret=True, use_kernel=True)
        racc, rvotes = ref.spfl_packed_aggregate_ref(
            sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
            s['sign_ok'], 200, 3)
        np.testing.assert_allclose(
            np.asarray(acc), np.asarray(racc), rtol=0,
            atol=ops.aggregate_atol(s['weight'], s['gmax'], gbar))
        if present:
            assert jnp.array_equal(votes, rvotes)
        else:
            assert votes is None


def test_decode_once_on_corrupted_buffers_matches_reference():
    """The bitlevel erasure path: damaged payload words feed the same
    kernel — parity must hold on garbage too (the PS uses whatever the
    verify flags let through)."""
    k, n, bits = 6, 1500, 3
    sign, qidx, sw_p, qw_p, gbar, s = _payloads(k, n, bits, seed=3)
    key = jax.random.PRNGKey(4)
    sw_c, _, _ = WC.corrupt_fold(key, sw_p, jnp.full((k,), 0.02))
    qw_c, _, _ = WC.corrupt_fold(jax.random.fold_in(key, 1), qw_p,
                                 jnp.full((k,), 0.02))
    acc, votes = ops.spfl_aggregate_packed(
        sw_c, qw_c, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits, interpret=True, use_kernel=True)
    racc, rvotes = ref.spfl_packed_aggregate_ref(
        sw_c, qw_c, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
        s['sign_ok'], n, bits)
    # corrupted headers can bitcast to huge ranges; bound by what the
    # decode actually produced rather than the clean-channel scalars
    np.testing.assert_allclose(
        np.asarray(acc), np.asarray(racc), rtol=0,
        atol=4 * np.finfo(np.float32).eps
        * max(1.0, float(jnp.sum(jnp.max(jnp.abs(jnp.stack(
            [acc, racc])), axis=0)) / n * k)))
    assert jnp.array_equal(votes, rvotes)


def test_flat_bitlevel_aggregate_matches_decode_per_client():
    """End-to-end: spfl bitlevel through the decode-once path equals the
    seed decode-per-client aggregation of the SAME received buffers."""
    k, l, bits = 6, 2000, 3
    g = jax.random.normal(jax.random.PRNGKey(5), (k, l)) * 0.02
    grads = jnp.where(g == 0, 1e-4, g)
    gbar = jnp.abs(grads[0])
    q = jnp.linspace(0.3, 0.9, k)
    p = jnp.linspace(0.4, 0.95, k)
    key = jax.random.PRNGKey(6)
    ghat, d = TR.spfl_aggregate(grads, gbar, q, p, bits, 64, key,
                                wire='packed', channel='bitlevel')
    # reference: replay the identical channel, decode per client, seq-mean
    kq, ko = jax.random.split(key)
    qg = TR._per_client_quantize(grads, bits, kq)
    sw, mw, _ = TR.encode_wire(qg, 0)
    rep = BC.transmit_uplink(ko, sw, mw, q, p, n=l, bits=bits)
    assert jnp.array_equal(rep.sign_ok, d.sign_ok)
    assert jnp.array_equal(rep.mod_ok, d.mod_ok)
    gmin, gmax = packets.mod_header_ranges(rep.mod_words)
    w = TR._inverse_prob(rep.sign_ok, q)
    racc, _ = ref.spfl_packed_aggregate_ref(
        packets.sign_payload(rep.sign_words),
        packets.mod_payload(rep.mod_words), gbar, gmin, gmax,
        rep.mod_ok.astype(jnp.float32), w, rep.sign_ok, l, bits)
    np.testing.assert_allclose(np.asarray(ghat), np.asarray(racc / k),
                               atol=1e-6, rtol=0)


# ---------------------------------------------------------------------------
# fused corruption: kernel == jnp twin == materialized reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('k,w', [(1, 40), (4, 513), (8, 1100)])
def test_corrupt_fold_kernel_matches_jnp_twin(k, w):
    rng = np.random.RandomState(k * 100 + w)
    words = jnp.asarray(rng.randint(0, 2 ** 32, (k, w), np.int64),
                        jnp.uint32)
    ber = jnp.asarray(rng.uniform(0.0, 0.2, k), jnp.float32)
    key = jax.random.PRNGKey(k + w)
    rx_k, fold_k, flips_k = ops.corrupt_fold_words(
        key, words, ber, interpret=True, use_kernel=True)
    rx_j, fold_j, flips_j = WC.corrupt_fold(key, words, ber)
    assert jnp.array_equal(rx_k, rx_j)               # bit-exact, all of it
    assert jnp.array_equal(fold_k, fold_j)
    assert jnp.array_equal(flips_k, flips_j)
    # and the loop-over-planes mask equals the materialized (..., W, 32)
    # reference retained for exactly this proof
    mask_ref = WC.flip_mask_ref(key, (k, w), ber)
    assert jnp.array_equal(rx_j ^ words, mask_ref)


def test_flip_mask_edges_and_no_32x_shape():
    key = jax.random.PRNGKey(0)
    words = jnp.asarray(np.random.RandomState(0).randint(
        0, 2 ** 32, (4, 64), np.int64), jnp.uint32)
    clean, m0 = WC.corrupt_words(key, words, jnp.zeros(4))
    assert jnp.array_equal(clean, words)
    assert int(jnp.sum(WC.count_flips(m0))) == 0
    allf, m1 = WC.corrupt_words(key, words, jnp.ones(4))
    assert jnp.array_equal(allf, ~words)             # ber=1 edge is exact
    # scalar ber broadcasts identically to per-client ber
    ms = WC.flip_mask(key, (4, 64), 0.03)
    mv = WC.flip_mask(key, (4, 64), jnp.full((4,), 0.03))
    assert jnp.array_equal(ms, mv)


def test_hash_rng_is_seed_sensitive_and_deterministic():
    words = jnp.zeros((2, 100), jnp.uint32)
    ber = jnp.full((2,), 0.1)
    a1 = WC.flip_mask(jax.random.PRNGKey(1), (2, 100), ber)
    a2 = WC.flip_mask(jax.random.PRNGKey(1), (2, 100), ber)
    b = WC.flip_mask(jax.random.PRNGKey(2), (2, 100), ber)
    assert jnp.array_equal(a1, a2)
    assert not jnp.array_equal(a1, b)
    del words


# ---------------------------------------------------------------------------
# the live verify path runs through the Pallas fold kernel
# ---------------------------------------------------------------------------

def test_transport_verify_uses_fold_words_kernel(monkeypatch):
    """The bit-level transports' PS verify must fold received buffers
    through kernels.ops.fold_words (the Pallas CRC kernel) and agree
    with the jnp reference predicate (packets.verify_* / format.xor_fold)."""
    calls = {'n': 0}
    real = ops.fold_words

    def spy(words, interpret=None, **kw):
        calls['n'] += 1
        out = real(words, interpret=interpret, **kw)
        assert jnp.array_equal(out, fmt.xor_fold(words))   # kernel == jnp
        return out

    monkeypatch.setattr(ops, 'fold_words', spy)
    k, l = 4, 600
    g = jax.random.normal(jax.random.PRNGKey(7), (k, l)) * 0.02
    grads = jnp.where(g == 0, 1e-4, g)
    gbar = jnp.abs(grads[0])
    q = p = jnp.full((k,), 0.6)
    _, d = TR.spfl_aggregate(grads, gbar, q, p, 3, 64,
                             jax.random.PRNGKey(8), wire='packed',
                             channel='bitlevel')
    assert calls['n'] >= 2                   # sign + modulus verify
    # the kernel-fold verify is the reference predicate, bit for bit
    kq, ko = jax.random.split(jax.random.PRNGKey(8))
    qg = TR._per_client_quantize(grads, 3, kq)
    sw, mw, _ = TR.encode_wire(qg, 0)
    rep = BC.transmit_uplink(ko, sw, mw, q, p, n=l, bits=3)
    assert jnp.array_equal(
        rep.sign_ok, packets.verify_sign_words(rep.sign_words, n=l))
    assert jnp.array_equal(
        rep.mod_ok, packets.verify_mod_words(rep.mod_words, n=l, bits=3))


def test_tree_bitlevel_uses_fused_corruption(monkeypatch):
    """The tree transport's channel pass goes through the fused
    corrupt+fold seam (ops.corrupt_fold_words)."""
    calls = {'n': 0}
    real = ops.corrupt_fold_words

    def spy(key, words, ber, **kw):
        calls['n'] += 1
        return real(key, words, ber, **kw)

    monkeypatch.setattr(TR.kops, 'corrupt_fold_words', spy)
    k = 4
    g = jax.random.normal(jax.random.PRNGKey(9), (k, 160)) * 0.02
    grads = jnp.where(g == 0, 1e-4, g)
    tree = {'a': grads[:, :64], 'b': grads[:, 64:]}
    gbar = jnp.abs(grads[0])
    gbar_tree = {'a': gbar[:64], 'b': gbar[64:]}
    fl = dataclasses.replace(FL, wire='packed', channel='bitlevel')
    TR.spfl_aggregate_tree(tree, gbar_tree, jnp.full((k,), 0.7),
                           jnp.full((k,), 0.6), fl, jax.random.PRNGKey(10))
    assert calls['n'] >= 4                   # 2 leaves x (sign + modulus)
