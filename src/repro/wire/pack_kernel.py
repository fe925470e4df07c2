"""Pallas TPU kernels for the bit-plane wire format.

The payload layout (repro.wire.format) was chosen to be kernel-shaped: a
group of 32 consecutive coordinates becomes ``bits`` words by pure
shift/mask/lane-reduce arithmetic, so pack and unpack are elementwise
VPU streams with zero cross-group communication.  Arrays enter as
group-major 2-D tiles — values ``(G, 32)``, words ``(G, bits)`` — and the
grid runs over blocks of ``BLOCK_GROUPS`` groups.

Kernels:

* ``pack_bits_kernel``      — values -> payload words.
* ``unpack_bits_kernel``    — payload words -> values.
* ``quantize_pack_kernel``  — the fused client-side pass: stochastic
                              quantization (paper eq. (7)-(8), identical
                              math to ``kernels.quantize_kernel``) +
                              sign/modulus packing in ONE read of the
                              gradient — quantize->pack with no int8/int32
                              intermediates touching HBM.
* ``unpack_dequant_kernel`` — the fused PS-side pass: unpack both packets
                              + knob reconstruction + compensation select
                              + 1/q weighting (eq. (15)-(17)) in one pass.
* ``fold_words_kernel``     — per-client, lane-wise xor-fold of a (K, W)
                              word buffer, accumulated across word-block
                              grid steps: the on-chip form of the CRC
                              reduction (format.xor_fold).  Live in the
                              PS verify path of the bit-level transports
                              (repro.core.bitchannel) since the
                              packed-domain hot-path PR.
* ``spfl_accumulate_kernel`` — the decode-once PS pass: extends
                              ``unpack_dequant_kernel`` with a client
                              grid dimension, so ONE kernel launch
                              unpacks, dequantizes, compensates,
                              1/q-weights and *accumulates* all K
                              clients' packed payloads into the f32
                              aggregate — the cross-client reduce never
                              materializes a (K, n) float intermediate.
                              On a client-sharded mesh the same kernel
                              is the shard-local stage of the sharded
                              collective: each device accumulates only
                              its K_local clients and a single
                              ``lax.psum`` finishes the sum
                              (``kernels.ops.spfl_aggregate_packed_sharded``),
                              so no client payload is ever all-gathered.
                              Sign votes ride along in the packed
                              domain: each client's sign bit-plane is
                              transposed into a per-coordinate vote word
                              (bit k = client k's sign) and a single
                              ``lax.population_count`` at the last
                              client-grid step turns it into counts.
* ``corrupt_fold_kernel``   — the on-chip bit channel: draws counter-PRF
                              random bits (repro.wire.corrupt.hash_bits),
                              thresholds them against the per-client BER,
                              packs the flip mask in-register, xors it
                              into the payload, and accumulates both the
                              mask's lane-wise xor-fold (fusing
                              fold_words_kernel's reduction into the same
                              pass) and its popcount — no (..., W, 32)
                              random tensor ever exists.  Off-TPU the
                              interpret-mode pallas_call doubles as a
                              fusion boundary,
                              stopping XLA CPU from re-running the hash
                              chain once per downstream consumer.

Per-launch scalars travel as (1, 1) blocks exactly like
``kernels.quantize_kernel``; the decode-once kernel's per-client scalars
sit whole in SMEM, indexed by the client grid step.  The kernels keep to
what the TPU lowering accepts: unsigned lane sums run as int32 (the
summed lanes hold disjoint bits), uint32 values reach f32 through an
int32 view (they are < 2^bits), and the xor-folds stay lane-wide in the
kernel, their last cross-lane xor done by the caller
(``format.xor_fold``).  Everything is validated against the
``format``/``corrupt`` references in interpret mode (tests/test_wire.py,
tests/test_bitchannel.py) and compiled for a v5e chip at real widths
(tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize_kernel import quantize_body
from repro.wire.corrupt import hash_bits
from repro.wire.format import GROUP, WORD_BITS

BLOCK_GROUPS = 256           # groups (of 32 values) per grid step
BLOCK_FOLD_WORDS = 512       # words per grid step of the fold reduction
BLOCK_CORRUPT_WORDS = 512    # words per grid step of the fused corruption
MAX_VOTE_CLIENTS = 32        # vote word capacity: one bit per client


def _scalar_spec():
    return pl.BlockSpec((1, 1), lambda i: (0, 0))


def _value_spec():
    return pl.BlockSpec((BLOCK_GROUPS, GROUP), lambda i: (i, 0))


def _word_spec(bits: int):
    return pl.BlockSpec((BLOCK_GROUPS, bits), lambda i: (i, 0))


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.uint32, shape, 1)


def _i32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _u32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _pack(v: jax.Array, bits: int) -> jax.Array:
    """(BG, 32) uint32 -> (BG, bits) words.  The lane reduction runs in
    int32 (the TPU lowering has no unsigned reductions): each lane holds
    a distinct bit, so the wrapping int32 sum equals the or."""
    lane = _lane(v.shape)
    planes = [jnp.sum(_i32(((v >> j) & jnp.uint32(1)) << lane), axis=1,
                      dtype=jnp.int32) for j in range(bits)]
    return _u32(jnp.stack(planes, axis=1))


def _unpack(w: jax.Array, bits: int) -> jax.Array:
    """(BG, bits) words -> (BG, 32) uint32 values."""
    lane = _lane((w.shape[0], GROUP))
    acc = jnp.zeros((w.shape[0], GROUP), jnp.uint32)
    for j in range(bits):
        plane = (w[:, j:j + 1] >> lane) & jnp.uint32(1)
        acc = acc | (plane << jnp.uint32(j))
    return acc


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def pack_bits_kernel(v_ref, w_ref, *, bits: int):
    w_ref[...] = _pack(v_ref[...].astype(jnp.uint32), bits)


def unpack_bits_kernel(w_ref, v_ref, *, bits: int):
    v_ref[...] = _unpack(w_ref[...].astype(jnp.uint32), bits)


def quantize_pack_kernel(gmin_ref, gmax_ref, g_ref, r_ref,
                         sw_ref, qw_ref, *, bits: int):
    """Fused eq. (7)-(8) + packing: gradient tile in, two packed-word
    tiles out."""
    g = g_ref[...].astype(jnp.float32)
    qidx = quantize_body(g, r_ref[...].astype(jnp.float32),
                         gmin_ref[0, 0], gmax_ref[0, 0], bits)
    sw_ref[...] = _pack((g >= 0.0).astype(jnp.uint32), 1)
    qw_ref[...] = _pack(_u32(qidx.astype(jnp.int32)), bits)


def _dequant_contrib(sw, qw, gbar, gmin, step, mod_ok, w, bits: int):
    """Shared decode body of the PS-side kernels: unpack both payload
    tiles, reconstruct w * s(g) ⊙ (mod_ok ? Q_v(g) : gbar).  The knob
    step arrives precomputed (a constant-divisor division in-kernel gets
    strength-reduced to a reciprocal multiply, drifting a ulp from the
    jnp dequantizer).  -> (sign bit tile (BG, 32) uint32, contribution
    tile (BG, 32) f32)."""
    sign_bits = _unpack(sw.astype(jnp.uint32), 1)
    sign = jnp.where(sign_bits > 0, 1.0, -1.0)
    # qidx < 2^bits: the int32 view is exact (no uint32 -> f32 cast on TPU)
    qidx = _i32(_unpack(qw.astype(jnp.uint32), bits)).astype(jnp.float32)
    modulus = gmin + qidx * step
    modulus = jnp.where(mod_ok > 0.0, modulus, gbar.astype(jnp.float32))
    return sign_bits, w * (sign * modulus)


def unpack_dequant_kernel(gmin_ref, step_ref, mod_ok_ref, weight_ref,
                          sw_ref, qw_ref, gbar_ref, out_ref, *, bits: int):
    """Fused PS decode, eq. (15)-(17):
    out = w * s(g) ⊙ (mod_ok ? Q_v(g) : gbar) straight from packed words."""
    _, contrib = _dequant_contrib(
        sw_ref[...], qw_ref[...], gbar_ref[...], gmin_ref[0, 0],
        step_ref[0, 0], mod_ok_ref[0, 0], weight_ref[0, 0], bits)
    out_ref[...] = contrib


def spfl_accumulate_kernel(gmin_ref, step_ref, mod_ok_ref, weight_ref,
                           vote_gate_ref, sw_ref, qw_ref, gbar_ref,
                           acc_ref, votes_ref, *, bits: int,
                           n_clients: int, with_votes: bool):
    """Decode-once eq. (15)-(17) over the client grid (axis 1): for every
    group block, unpack client k's packed payloads, reconstruct
    w_k * s_k ⊙ (mod_ok_k ? Q_v(g_k) : gbar), and accumulate into the
    f32 aggregate — grid step (i, 0) initializes, (i, k>0) adds, so the
    cross-client sum happens in VMEM without a (K, n) intermediate.

    Votes stay packed: client k's sign bits are or'ed into bit k of a
    per-coordinate vote word (gated by vote_gate = sign_ok), and the
    final client step converts the transposed word to counts with one
    ``lax.population_count`` per bit-plane.  ``with_votes`` is static —
    False (K beyond the 32-client vote word) skips all vote work at
    trace time and only zero-fills the output once.

    The knob step arrives precomputed (see ``_dequant_contrib``).
    """
    k = pl.program_id(1)
    sign_bits, contrib = _dequant_contrib(
        sw_ref[...], qw_ref[...], gbar_ref[...], gmin_ref[k],
        step_ref[k], mod_ok_ref[k], weight_ref[k], bits)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = contrib
        if not with_votes:
            votes_ref[...] = jnp.zeros(votes_ref.shape, jnp.uint32)

    @pl.when(k != 0)
    def _acc():
        acc_ref[...] = acc_ref[...] + contrib

    if with_votes:
        voted = sign_bits * vote_gate_ref[k]

        @pl.when(k == 0)
        def _init_votes():
            votes_ref[...] = voted

        @pl.when(k != 0)
        def _acc_votes():
            votes_ref[...] = votes_ref[...] | (
                voted << k.astype(jnp.uint32))

        @pl.when(k == n_clients - 1)
        def _finalize_votes():
            votes_ref[...] = jax.lax.population_count(votes_ref[...])


def corrupt_fold_kernel(seed_ref, off_ref, thresh_ref, allflip_ref, w_ref,
                        rx_ref, fold_ref, flips_ref, *, n_words: int):
    """Fused bit channel: counter-PRF draw -> threshold -> in-register
    pack -> xor into the payload, with the flip mask's xor-fold
    (fold_words_kernel's reduction) and popcount accumulated lane-wise in
    the same pass.  ``n_words`` is the true (unpadded) buffer width: the
    global word index matches the jnp reference exactly and padding
    columns never flip.  ``off_ref`` is the buffer's word offset in the global
    counter stream (``first_row * n_words`` on a client-sharded slice —
    the sharded channel draws the same bits the gathered one would)."""
    j = pl.program_id(0)
    words = w_ref[...].astype(jnp.uint32)
    k_row = jax.lax.broadcasted_iota(jnp.uint32, words.shape, 0)
    col = (jax.lax.broadcasted_iota(jnp.uint32, words.shape, 1)
           + jnp.uint32(j * BLOCK_CORRUPT_WORDS))
    valid = (col < jnp.uint32(n_words)).astype(jnp.uint32)
    base = k_row * jnp.uint32(n_words) + col + off_ref[0, 0]
    thresh = thresh_ref[...].astype(jnp.uint32)          # (K, 1)
    allf = allflip_ref[...].astype(jnp.uint32)           # (K, 1)
    s0 = seed_ref[0, 0]
    s1 = seed_ref[0, 1]
    mask = jnp.zeros(words.shape, jnp.uint32)
    for b in range(WORD_BITS):
        h = hash_bits(base, b, s0, s1)
        bit = (((h < thresh).astype(jnp.uint32) | allf) & valid)
        mask = mask | (bit << jnp.uint32(b))
    rx_ref[...] = words ^ mask
    flips = _i32(jax.lax.population_count(mask))

    @pl.when(j == 0)
    def _init():
        fold_ref[...] = mask
        flips_ref[...] = flips

    @pl.when(j != 0)
    def _acc():
        fold_ref[...] = fold_ref[...] ^ mask
        flips_ref[...] = flips_ref[...] + flips


def fold_words_kernel(w_ref, f_ref):
    """Xor one (K, BLOCK_FOLD_WORDS) block into the lane-wide (K,
    BLOCK_FOLD_WORDS) accumulator; grid step 0 initializes, later steps
    accumulate (xor is associative/commutative, so block order is
    irrelevant).  The last cross-lane fold happens outside the kernel:
    the TPU lowering has no xor reduction."""
    w = w_ref[...].astype(jnp.uint32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        f_ref[...] = w

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        f_ref[...] = f_ref[...] ^ w


# ---------------------------------------------------------------------------
# pallas_call builders (group-major 2-D inputs, grid over group blocks)
# ---------------------------------------------------------------------------

def _grid(n_rows: int):
    assert n_rows % BLOCK_GROUPS == 0, n_rows
    return (n_rows // BLOCK_GROUPS,)


@functools.partial(jax.jit, static_argnames=('bits', 'interpret'))
def pack_2d(values, *, bits: int, interpret: bool = False):
    """values: (G, 32) uint32 -> (G, bits) uint32 words."""
    return pl.pallas_call(
        functools.partial(pack_bits_kernel, bits=bits),
        grid=_grid(values.shape[0]),
        in_specs=[_value_spec()],
        out_specs=_word_spec(bits),
        out_shape=jax.ShapeDtypeStruct((values.shape[0], bits), jnp.uint32),
        interpret=interpret,
    )(values)


@functools.partial(jax.jit, static_argnames=('bits', 'interpret'))
def unpack_2d(words, *, bits: int, interpret: bool = False):
    """words: (G, bits) uint32 -> (G, 32) uint32 values."""
    return pl.pallas_call(
        functools.partial(unpack_bits_kernel, bits=bits),
        grid=_grid(words.shape[0]),
        in_specs=[_word_spec(bits)],
        out_specs=_value_spec(),
        out_shape=jax.ShapeDtypeStruct((words.shape[0], GROUP), jnp.uint32),
        interpret=interpret,
    )(words)


@functools.partial(jax.jit, static_argnames=('bits', 'interpret'))
def quantize_pack_2d(g, rand, gmin, gmax, *, bits: int,
                     interpret: bool = False):
    """g, rand: (G, 32) f32; gmin/gmax: (1, 1).
    -> (sign words (G, 1), qidx words (G, bits)), both uint32."""
    n_rows = g.shape[0]
    return pl.pallas_call(
        functools.partial(quantize_pack_kernel, bits=bits),
        grid=_grid(n_rows),
        in_specs=[_scalar_spec(), _scalar_spec(), _value_spec(),
                  _value_spec()],
        out_specs=[_word_spec(1), _word_spec(bits)],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, 1), jnp.uint32),
            jax.ShapeDtypeStruct((n_rows, bits), jnp.uint32),
        ],
        interpret=interpret,
    )(gmin, gmax, g, rand)


@functools.partial(jax.jit, static_argnames=('interpret',))
def fold_words_2d(words, *, interpret: bool = False):
    """words: (K, W) uint32 with W a BLOCK_FOLD_WORDS multiple
    -> (K, BLOCK_FOLD_WORDS) lane-wise xor-folds (``format.xor_fold``
    over the last axis finishes the per-client fold)."""
    k, w_n = words.shape
    assert w_n % BLOCK_FOLD_WORDS == 0, w_n
    spec = pl.BlockSpec((k, BLOCK_FOLD_WORDS), lambda j: (0, j))
    return pl.pallas_call(
        fold_words_kernel,
        grid=(w_n // BLOCK_FOLD_WORDS,),
        in_specs=[spec],
        out_specs=pl.BlockSpec((k, BLOCK_FOLD_WORDS), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, BLOCK_FOLD_WORDS), jnp.uint32),
        interpret=interpret,
    )(words)


@functools.partial(jax.jit, static_argnames=('bits', 'n_clients',
                                             'gbar_per_client',
                                             'with_votes', 'interpret'))
def spfl_accumulate_2d(sign_words, qidx_words, gbar, gmin, step, mod_ok,
                       weight, vote_gate, *, bits: int, n_clients: int,
                       gbar_per_client: bool, with_votes: bool = True,
                       interpret: bool = False):
    """Decode-once aggregation over the client grid.

    sign_words (K*G_pad, 1) / qidx_words (K*G_pad, bits): every client's
    padded group-major payload stacked along rows; gbar (G_pad, 32)
    shared or (K*G_pad, 32) per-client; per-client scalars (K,), whole
    in SMEM and indexed by the client grid step (vote_gate uint32 0/1 =
    sign_ok, ``step`` the precomputed knob step, the rest f32).
    -> (client-sum (G_pad, 32) f32, sign votes (G_pad, 32) uint32).
    """
    rows = sign_words.shape[0] // n_clients
    gb = rows // BLOCK_GROUPS            # group blocks per client
    assert gb * BLOCK_GROUPS == rows, (sign_words.shape, n_clients)
    scal = pl.BlockSpec(memory_space=pltpu.SMEM)
    pay = lambda width: pl.BlockSpec((BLOCK_GROUPS, width),
                                     lambda i, k: (k * gb + i, 0))
    gbar_spec = pay(GROUP) if gbar_per_client else \
        pl.BlockSpec((BLOCK_GROUPS, GROUP), lambda i, k: (i, 0))
    out_spec = pl.BlockSpec((BLOCK_GROUPS, GROUP), lambda i, k: (i, 0))
    return pl.pallas_call(
        functools.partial(spfl_accumulate_kernel, bits=bits,
                          n_clients=n_clients, with_votes=with_votes),
        grid=(gb, n_clients),            # clients innermost: accumulation
        in_specs=[scal] * 5 + [pay(1), pay(bits), gbar_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, GROUP), jnp.float32),
                   jax.ShapeDtypeStruct((rows, GROUP), jnp.uint32)],
        interpret=interpret,
    )(gmin, step, mod_ok, weight, vote_gate, sign_words, qidx_words, gbar)


@functools.partial(jax.jit, static_argnames=('n_words', 'interpret'))
def corrupt_fold_2d(seeds, word0, thresh, allflip, words, *, n_words: int,
                    interpret: bool = False):
    """Fused corruption of (K, W_pad) word buffers (W_pad a
    BLOCK_CORRUPT_WORDS multiple; columns >= n_words never flip).
    seeds (1, 2) uint32; word0 (1, 1) uint32 global word offset;
    thresh/allflip (K, 1) uint32.
    -> (received (K, W_pad), lane-wise mask xor-folds (K,
    BLOCK_CORRUPT_WORDS) uint32, lane-wise flip counts (K,
    BLOCK_CORRUPT_WORDS) int32); the caller finishes both lane
    reductions (the TPU lowering has no xor reduction)."""
    k, w_pad = words.shape
    assert w_pad % BLOCK_CORRUPT_WORDS == 0, w_pad
    flag_spec = pl.BlockSpec((k, 1), lambda j: (0, 0))
    acc_spec = pl.BlockSpec((k, BLOCK_CORRUPT_WORDS), lambda j: (0, 0))
    return pl.pallas_call(
        functools.partial(corrupt_fold_kernel, n_words=n_words),
        grid=(w_pad // BLOCK_CORRUPT_WORDS,),
        in_specs=[pl.BlockSpec((1, 2), lambda j: (0, 0)),
                  pl.BlockSpec((1, 1), lambda j: (0, 0)),
                  flag_spec, flag_spec,
                  pl.BlockSpec((k, BLOCK_CORRUPT_WORDS), lambda j: (0, j))],
        out_specs=[pl.BlockSpec((k, BLOCK_CORRUPT_WORDS), lambda j: (0, j)),
                   acc_spec, acc_spec],
        out_shape=[jax.ShapeDtypeStruct((k, w_pad), jnp.uint32),
                   jax.ShapeDtypeStruct((k, BLOCK_CORRUPT_WORDS), jnp.uint32),
                   jax.ShapeDtypeStruct((k, BLOCK_CORRUPT_WORDS),
                                        jnp.int32)],
        interpret=interpret,
    )(seeds, word0, thresh, allflip, words)


@functools.partial(jax.jit, static_argnames=('bits', 'interpret'))
def unpack_dequant_2d(sign_words, qidx_words, gbar, gmin, step, mod_ok,
                      weight, *, bits: int, interpret: bool = False):
    """sign_words (G, 1), qidx_words (G, bits), gbar (G, 32), precomputed
    knob step (1, 1) -> (G, 32) f32."""
    n_rows = sign_words.shape[0]
    return pl.pallas_call(
        functools.partial(unpack_dequant_kernel, bits=bits),
        grid=_grid(n_rows),
        in_specs=[_scalar_spec()] * 4
        + [_word_spec(1), _word_spec(bits), _value_spec()],
        out_specs=_value_spec(),
        out_shape=jax.ShapeDtypeStruct((n_rows, GROUP), jnp.float32),
        interpret=interpret,
    )(gmin, step, mod_ok, weight, sign_words, qidx_words, gbar)
