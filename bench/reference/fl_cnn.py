"""Plain reference of the SP-FL round on the paper's CIFAR CNN.

It follows every round of one fused segment from the benchmark's own
inputs (client data and initial weights made from the seed) and the seed's
simulation streams, written straight from the paper's algorithm and the
wire format's specification:

1. per-client loss and gradient of the §V CNN (float32 at `highest`
   precision; the control stores weights, activations and gradients in
   bfloat16);
2. the AR(1) shadowing step of the per-round channel gains;
3. the eq. (28) allocation, in float64 NumPy (`reference.allocation`),
   uniform while the compensation vector is still zero: its optimum, and
   the objective of the (q, p) the round transmits with;
4. the transport: per-client stochastic 3-bit quantization, and either
   the packed wire (framed uint32 sign and modulus packets, counter-hash
   bit flips at the BER calibrated to (q, p), xor-fold verification, decode
   of what was received) or the analytic Bernoulli packet draws;
5. the aggregate, eq. (17), the SGD update and the compensation roll.

Given the (q, p) that a run under check transmitted with, the reference
transmits with them too (`forced`): the allocation is held to the float64
optimum by its objective, which is continuous where the argmin is not, and
the transport and update are compared round by round on the same packets.

Nothing here imports the program under test.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import allocation as ref_alloc

# pytree order of the CNN's parameter dict (flattening sorts the keys)
LEAVES = ('conv1_b', 'conv1_w', 'conv2_b', 'conv2_w', 'fc1_b', 'fc1_w',
          'fc2_b', 'fc2_w', 'fc3_b', 'fc3_w')
SHAPES = {'conv1_w': (5, 5, 3, 6), 'conv1_b': (6,),
          'conv2_w': (5, 5, 6, 16), 'conv2_b': (16,),
          'fc1_w': (400, 120), 'fc1_b': (120,),
          'fc2_w': (120, 84), 'fc2_b': (84,),
          'fc3_w': (84, 10), 'fc3_b': (10,)}

# wire format: packet framing and the bit channel's counter hash
SIGN_MAGIC, MOD_MAGIC = 0x53474E31, 0x4D4F4431
SIGN_HEADER, MOD_HEADER = 4, 7
_MIX1, _MIX2, _GOLDEN, _SALT = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9, 0x9E3779B1
_THRESH_MAX = 4294967040.0
_Q_FLOOR = 1e-8

# channel constants of the shadowing track
RHO, SHADOW_DB, MIN_DIST_M = 0.9, 4.0, 10.0


def init_params(key) -> Dict[str, jax.Array]:
    """N(0, 1/fan_in) weights, zero biases — made by the benchmark."""
    ks = jax.random.split(key, 5)
    out = {}
    for i, name in enumerate(('conv1', 'conv2', 'fc1', 'fc2', 'fc3')):
        shape = SHAPES[name + '_w']
        fan_in = int(np.prod(shape[:-1]))
        out[name + '_w'] = (jax.random.normal(ks[i], shape, jnp.float32)
                            / np.sqrt(fan_in))
        out[name + '_b'] = jnp.zeros(SHAPES[name + '_b'], jnp.float32)
    return out


def flatten(params) -> jax.Array:
    return jnp.concatenate([params[k].reshape(-1) for k in LEAVES])


def unflatten(flat) -> Dict[str, jax.Array]:
    out, at = {}, 0
    for k in LEAVES:
        n = int(np.prod(SHAPES[k]))
        out[k] = flat[at:at + n].reshape(SHAPES[k])
        at += n
    return out


def _forward(p, x, precision):
    def conv(h, w):
        return jax.lax.conv_general_dilated(
            h, w, (1, 1), 'VALID',
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=precision)

    def dot(h, w):
        return jnp.dot(h, w, precision=precision)

    def pool(h):
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), 'VALID')

    h = pool(jax.nn.relu(conv(x, p['conv1_w']) + p['conv1_b']))
    h = pool(jax.nn.relu(conv(h, p['conv2_w']) + p['conv2_b']))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(dot(h, p['fc1_w']) + p['fc1_b'])
    h = jax.nn.relu(dot(h, p['fc2_w']) + p['fc2_b'])
    return dot(h, p['fc3_w']) + p['fc3_b']


def _loss(p, x, y, precision):
    logits = _forward(p, x, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# the storage type of weights, activations and gradients: 'float32' is
# the reference, 'bfloat16' the control (the step below the float32 the
# configuration states)
STORAGE = {'float32': jnp.float32, 'bfloat16': jnp.bfloat16}


def make_grad_fn(dtype: str):
    """(flat params, xs (K,B,32,32,3), ys (K,B)) -> (losses (K,), grads
    (K, l) float32).  The reference computes in float32 at `highest`
    precision; the control stores the weights, the activations and the
    gradients in bfloat16.  One client's program, called client by
    client."""
    store = STORAGE[dtype]
    prec = jax.lax.Precision.HIGHEST

    @jax.jit
    def client(flat, x, y):
        def f(fl):
            return _loss(unflatten(fl), x.astype(store), y, prec)
        loss, grad = jax.value_and_grad(f)(flat.astype(store))
        return loss.astype(jnp.float32), grad.astype(jnp.float32)

    def grads(flat, xs, ys):
        out = [client(flat, xs[i], ys[i]) for i in range(xs.shape[0])]
        return (jnp.stack([o[0] for o in out]),
                jnp.stack([o[1] for o in out]))
    return grads


# --------------------------------------------------------------------------
# channel geometry and the shadowing track
# --------------------------------------------------------------------------

def static_gains(seed_key, k, radius_m, zeta):
    u = jax.random.uniform(jax.random.fold_in(seed_key, 1), (k,))
    dist = np.asarray(jnp.sqrt(MIN_DIST_M ** 2
                               + (radius_m ** 2 - MIN_DIST_M ** 2) * u))
    return jnp.asarray(dist ** (-zeta), jnp.float32)


def shadow_init(seed_key, k):
    return jax.random.normal(jax.random.fold_in(seed_key, 0x0FAD), (k,),
                             jnp.float32)


def shadow_step(kr, z):
    c = jnp.sqrt(jnp.asarray(1.0 - RHO ** 2, jnp.float32))
    return RHO * z + c * jax.random.normal(jax.random.fold_in(kr, 0x5AD0),
                                           z.shape, jnp.float32)


def shadow_gains(base, z):
    return base * 10.0 ** (SHADOW_DB * z / 10.0)


# --------------------------------------------------------------------------
# quantizer, wire and bit channel
# --------------------------------------------------------------------------

def quantize(grads, bits, key):
    """Per-client stochastic quantization, eq. (8): -> (qidx, gmin, gmax)."""
    a = jnp.abs(grads)
    gmin = jnp.min(a, axis=1, keepdims=True)
    gmax = jnp.max(a, axis=1, keepdims=True)
    levels = 2 ** bits - 1
    step = (gmax - gmin) / levels
    u = jnp.where(step > 0, (a - gmin) / jnp.where(step > 0, step, 1.0), 0.0)
    lower = jnp.clip(jnp.floor(u), 0, levels)
    rnd = jax.random.uniform(key, grads.shape, jnp.float32)
    qidx = jnp.clip(lower + (rnd < u - lower), 0, levels).astype(jnp.int32)
    return qidx, gmin[:, 0], gmax[:, 0]


def expected_quant_mse(grads, bits):
    """E||Q(g) - g||^2 per client (float64 on the host)."""
    g = np.abs(np.asarray(grads, np.float64))
    gmin = g.min(axis=1, keepdims=True)
    gmax = g.max(axis=1, keepdims=True)
    step = (gmax - gmin) / (2 ** bits - 1)
    safe = np.where(step > 0, step, 1.0)
    u = np.where(step > 0, (g - gmin) / safe, 0.0)
    frac = u - np.floor(u)
    return np.sum(step ** 2 * frac * (1.0 - frac), axis=1)


def pack(values, bits):
    """(K, n) ints -> (K, ceil(n/32)*bits) uint32: word g*bits+j holds bit
    plane j of coordinates 32g..32g+31, coordinate 32g+i in lane i."""
    k, n = values.shape
    groups = -(-n // 32)
    v = jnp.pad(values.astype(jnp.uint32), ((0, 0), (0, groups * 32 - n)))
    v = v.reshape(k, groups, 32)
    lane = jnp.arange(32, dtype=jnp.uint32)
    planes = [jnp.sum(((v >> j) & 1) << lane, axis=-1, dtype=jnp.uint32)
              for j in range(bits)]
    return jnp.stack(planes, axis=-1).reshape(k, groups * bits)


def unpack(words, n, bits):
    k, w = words.shape
    groups = w // bits
    wv = words.reshape(k, groups, bits)
    lane = jnp.arange(32, dtype=jnp.uint32)
    acc = jnp.zeros((k, groups, 32), jnp.uint32)
    for j in range(bits):
        acc = acc | (((wv[..., j:j + 1] >> lane) & 1) << j)
    return acc.reshape(k, groups * 32)[:, :n]


def _fold(words):
    return jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_xor, (1,))


def frames(header_cols, payload):
    """Header columns (list of (K,) uint32) + payload (K, W) + crc word."""
    body = jnp.concatenate(
        [jnp.stack([jnp.asarray(h).astype(jnp.uint32) for h in header_cols],
                   axis=1), payload], axis=1)
    return jnp.concatenate([body, _fold(body)[:, None]], axis=1)


def ber_for_success(prob, n_words):
    """Per-bit flip rate at which a 32-column xor-fold over n_words passes
    with probability prob: P = ((1 + (1-2e)^B)/2)^32, inverted."""
    prob = jnp.clip(jnp.asarray(prob, jnp.float32), 0.0, 1.0)
    rm1 = jnp.maximum(2.0 * jnp.expm1(jnp.log(prob) / 32), -1.0)
    return -0.5 * jnp.expm1(jnp.log1p(rm1) / n_words)


def _fmix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_MIX1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_MIX2)
    return x ^ (x >> 16)


def flip_mask(key, shape, ber):
    """Counter-hash flip mask: bit j of global word i flips iff
    fmix(fmix((i + GOLDEN) ^ s0) ^ s1 ^ j*SALT) < round(ber * 2^32)."""
    s = jax.random.bits(key, (2,), jnp.uint32)
    ber = jnp.asarray(ber, jnp.float32)
    t = jnp.clip(jnp.round(jnp.clip(ber, 0.0, 1.0) * 4294967296.0), 0.0,
                 _THRESH_MAX).astype(jnp.uint32)[:, None]
    allf = (ber >= 1.0)[:, None]
    idx = jnp.arange(shape[0] * shape[1], dtype=jnp.uint32).reshape(shape)
    h0 = _fmix((idx + jnp.uint32(_GOLDEN)) ^ s[0])
    mask = jnp.zeros(shape, jnp.uint32)
    for j in range(32):
        h = _fmix(h0 ^ s[1] ^ (jnp.uint32(j) * jnp.uint32(_SALT)))
        mask = mask | (((h < t) | allf).astype(jnp.uint32) << j)
    return mask


def f32_bits(x):
    return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32),
                                        jnp.uint32)


def bits_f32(w):
    return jax.lax.bitcast_convert_type(w, jnp.float32)


# --------------------------------------------------------------------------
# one round of transport
# --------------------------------------------------------------------------

def _transport(grads, gbar, q, p, key, n, bits, wire):
    k, l = grads.shape
    kq, ko = jax.random.split(key)
    qidx, gmin, gmax = quantize(grads, bits, kq)
    q_eff = 1.0 - (1.0 - q)
    w_of = jnp.where(q_eff > _Q_FLOOR, 1.0 / jnp.maximum(q_eff, _Q_FLOOR),
                     0.0)
    levels = 2 ** bits - 1
    if wire == 'packed':
        groups = -(-l // 32)
        ws, wm = SIGN_HEADER + groups + 1, MOD_HEADER + groups * bits + 1
        cid = jnp.arange(k, dtype=jnp.uint32)
        rnd = jnp.full((k,), n, jnp.uint32) & jnp.uint32((1 << 24) - 1)
        sign_bits = (grads >= 0).astype(jnp.uint32)
        fs = frames([jnp.full((k,), SIGN_MAGIC, jnp.uint32), cid, rnd,
                     jnp.full((k,), l, jnp.uint32)], pack(sign_bits, 1))
        fm = frames([jnp.full((k,), MOD_MAGIC, jnp.uint32), cid, rnd,
                     jnp.full((k,), l, jnp.uint32),
                     jnp.full((k,), bits, jnp.uint32),
                     f32_bits(gmin), f32_bits(gmax)], pack(qidx, bits))
        ks, kv = jax.random.split(ko)
        rs = fs ^ flip_mask(ks, fs.shape, ber_for_success(q, ws))
        rm = fm ^ flip_mask(kv, fm.shape, ber_for_success(p, wm))
        sign_ok = ((rs[:, 0] == SIGN_MAGIC) & (rs[:, 3] == l)
                   & (_fold(rs) == 0))
        mod_ok = ((rm[:, 0] == MOD_MAGIC) & (rm[:, 3] == l)
                  & (rm[:, 4] == bits) & (_fold(rm) == 0))
        sign = jnp.where(unpack(rs[:, SIGN_HEADER:-1], l, 1) > 0, 1.0, -1.0)
        rq = unpack(rm[:, MOD_HEADER:-1], l, bits).astype(jnp.float32)
        rmin, rmax = bits_f32(rm[:, 5]), bits_f32(rm[:, 6])
        modulus = rmin[:, None] + rq * ((rmax - rmin) / levels)[:, None]
        votes = jnp.sum(sign_ok[:, None] & (sign > 0), axis=0)
        n_ok = jnp.sum(sign_ok.astype(jnp.float32))
        agreement = jnp.where(
            n_ok > 0, jnp.mean(jnp.abs(2.0 * votes - n_ok))
            / jnp.maximum(n_ok, 1.0), jnp.nan)
    else:
        k1, k2 = jax.random.split(ko)
        sign_ok = jax.random.uniform(k1, q.shape) < q_eff
        mod_ok = jax.random.uniform(k2, p.shape) < p
        sign = jnp.sign(grads)
        modulus = (gmin[:, None]
                   + qidx.astype(jnp.float32) * ((gmax - gmin) / levels)[:, None])
        agreement = jnp.float32(jnp.nan)
    modulus = jnp.where(mod_ok[:, None], modulus, gbar[None, :])
    w = sign_ok.astype(jnp.float32) * w_of
    acc = w[0] * (sign[0] * modulus[0])
    for i in range(1, k):
        acc = acc + w[i] * (sign[i] * modulus[i])
    return acc / k, sign_ok, mod_ok, agreement


# --------------------------------------------------------------------------
# the rounds of one segment
# --------------------------------------------------------------------------

def run(seed: int, params0, xs, ys, fl: dict, wire: str, n_rounds: int,
        dtype: str = 'float32', order=None, forced=None) -> dict:
    """Follow ``n_rounds`` rounds from ``params0`` on client data ``xs``,
    ``ys``.  ``fl`` holds the operating point (see the configuration file);
    ``order`` is the run's order of the clients, whose placement and
    shadowing state follow them; ``forced``, where given, is the list of
    per-round (q, p) to transmit with instead of the float64 solution.

    Returns per round the mean client loss, the (q, p) transmitted with,
    their eq. (28) objective on this run's own statistics (`objective`)
    and the sum of its terms' magnitudes (`objective_scale`), the float64
    optimum (`optimum`; NaN in uniform rounds), packet
    outcomes and sign agreement; and after the last round the flat
    parameters, the compensation vector and the round-0 mean client
    gradient.

    It runs on the host's CPU backend, where float32 is float32 (a TPU
    rounds float32 products to bfloat16 passes, and compiling its
    six-pass `highest` convolutions takes minutes)."""
    cpu = jax.devices('cpu')[0]
    with jax.default_device(cpu):
        return _run(seed, jax.device_put(params0, cpu),
                    jax.device_put(xs, cpu), jax.device_put(ys, cpu), fl,
                    wire, n_rounds, dtype,
                    np.arange(xs.shape[0]) if order is None else order,
                    forced)


def _run(seed, params0, xs, ys, fl, wire, n_rounds, dtype, order, forced):
    k = xs.shape[0]
    bits = fl['quant_bits']
    lr = fl['learning_rate']
    store = STORAGE[dtype]
    seed_key = jax.random.PRNGKey(seed)
    base = static_gains(seed_key, k, fl['cell_radius_m'],
                        fl['path_loss_exp'])[order]
    z = shadow_init(seed_key, k)[order]
    p_w = np.full(k, 10 ** (fl['tx_power_dbm'] / 10) / 1000.0)
    chan = dict(fl, noise_psd_w=10 ** (fl['noise_psd_dbm'] / 10) / 1000.0,
                lipschitz=1.0 / lr)
    grad_fn = make_grad_fn(dtype)
    transport = jax.jit(_transport, static_argnums=(6, 7))
    flat = flatten(params0).astype(store)
    dim = flat.shape[0]
    gbar = jnp.zeros((dim,), jnp.float32)
    key = seed_key
    out: Dict[str, List] = {'loss': [], 'q': [], 'p': [], 'sign_ok': [],
                            'mod_ok': [], 'agreement': [], 'objective': [],
                            'objective_scale': [], 'optimum': []}
    for n in range(n_rounds):
        key, kr = jax.random.split(key)
        losses, grads = grad_fn(flat, xs, ys)
        if n == 0:
            out['grad0'] = np.asarray(jnp.mean(grads, axis=0))
        z = shadow_step(kr, z)
        gains = shadow_gains(base, z)
        g64 = np.asarray(grads, np.float64)
        gb64 = np.asarray(gbar, np.float64)
        gb2 = np.full(k, np.sum(gb64 ** 2))
        prob = ref_alloc.Problem(
            np.sum(g64 ** 2, axis=1), gb2, np.abs(g64) @ gb64,
            expected_quant_mse(grads, bits), np.asarray(gains, np.float64),
            p_w, dim, chan)
        solved = gb2.max() > 0
        q, p, alpha, beta, _ = ref_alloc.solve(
            prob, 'alternating' if solved else 'uniform',
            max_iters=fl['allocation_max_iters'], tol=fl['allocation_tol'])
        out['optimum'].append(prob.objective(alpha, beta) if solved
                              else float('nan'))
        if forced is not None:
            q, p = forced[n]
        q = jnp.asarray(q, jnp.float32)
        p = jnp.asarray(p, jnp.float32)
        terms = prob.objective_terms(np.asarray(q), np.asarray(p))
        out['objective'].append(float(np.sum(terms)))
        out['objective_scale'].append(float(np.sum(np.abs(terms))))
        ghat, sign_ok, mod_ok, agree = transport(
            grads, gbar, q, p, kr, jnp.uint32(n), bits, wire)
        flat = (flat.astype(jnp.float32) - lr * ghat).astype(store)
        gbar = jnp.abs(ghat)
        out['loss'].append(float(jnp.mean(losses)))
        out['q'].append(np.asarray(q))
        out['p'].append(np.asarray(p))
        out['sign_ok'].append(np.asarray(sign_ok))
        out['mod_ok'].append(np.asarray(mod_ok))
        out['agreement'].append(float(agree))
    out['params'] = np.asarray(flat, np.float32)
    out['gbar'] = np.asarray(gbar)
    return out
