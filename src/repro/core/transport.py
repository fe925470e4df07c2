"""Gradient transports — the uplink of one FL round, as infrastructure.

This is the paper's contribution recast as a composable abstraction: a
*transport* consumes per-client gradients and produces the aggregated
global gradient the PS would have decoded, simulating the wireless uplink
(packetization, fading outcomes, compensation, inverse-probability
scaling).  It is a drop-in replacement for the all-reduce of data-parallel
training, which is how the same code serves both the paper-scale CNN
simulator and the LLM-scale distributed step (DESIGN.md §3).

Implemented transports (paper §V baselines):

* ``spfl``        — sign/modulus decoupled packets + sign-packet reuse with
                    compensation + 1/q unbiasing, eq. (15)–(17).
* ``spfl_retx``   — SP-FL with one sign-packet retransmission (Fig. 6).
* ``dds``         — single packet per client, uniform bandwidth, erroneous
                    gradients discarded [29].
* ``onebit``      — sign-only uplink, errors discarded [28].
* ``scheduling``  — top channel-gain subset (75%) scheduled, others idle
                    [46].
* ``error_free``  — quantized but lossless uplink (upper bound).

Flat (K, l) versions power the paper-scale simulator and tests; the
``*_tree`` variants apply the identical math leaf-wise over per-client
gradient pytrees with *shared per-client* quantizer ranges and packet
outcomes — exactly one "radio" per client per round, regardless of how the
model is sharded.

Wire materialization (``wire='packed'``): ``spfl`` and ``error_free`` can
route the quantized gradient through the real bit-packed packet layer
(repro.wire) — encode to framed uint32 word buffers and aggregate
straight from them.  The aggregation math is identical (the decode is
exact), and ``payload_bits`` becomes the *measured* size of the
materialized buffers instead of the analytic formula.
``wire='analytic'`` (default) keeps the original count-only path.

Decode-once hot path: the packed transports never unpack per client.
The PS decodes only the O(K) header words (the b0 range side-channel)
and hands the K stacked payload buffers to ONE fused kernel launch
(``kernels.ops.spfl_aggregate_packed``) that unpacks, dequantizes,
compensates, 1/q-weights and accumulates all K clients over a client
grid — so the cross-client collective moves ~(1+b)-bit/coordinate words
instead of f32/bf16 leaves and no (K, n) float intermediate exists.
Decoded signs/knobs/votes are bit-exact vs the retained
unpack-per-client reference (``kernels.ref.spfl_packed_aggregate_ref``);
the f32 reconstruction agrees to within a couple of ulp — the backend
contracts the kernel's fused mul+add chains into FMAs (fewer roundings,
not reproducible op-by-op from uncompiled jnp), and the analytic paths
accumulate clients in the same sequential order (``_seq_client_mean``)
so that bounded FMA wobble is the *only* difference.

Bit-level channel (``channel='bitlevel'``, packed wire only): decode
stops being lossless — the buffers take calibrated per-bit flips
(repro.core.bitchannel) and ``sign_ok``/``mod_ok`` are the PS-side
xor-fold verification outcomes of the damaged words, with the marginal
packet-error rates still matching eq. (11)/(13).  ``spfl_retx`` then
resends *materialized* sign buffers (same payload, fresh header stamp,
fresh draw) and the diagnostics carry per-client CRC state.  The
analytic baselines (dds/onebit/scheduling) honor the knob too: their
single-packet success probabilities route through the same calibration
(``bitchannel.calibrated_success_prob``) so all frameworks share one
channel model in cross-framework comparisons.

Sharded collective (``collective='sharded'``, packed wire + a mesh):
the decode-once kernel consumes full (K, W) buffers, which GSPMD can
only satisfy on a client-sharded mesh by all-gathering every client's
packed payload — forfeiting the ~12x byte win at exactly the scale it
targets.  With ``collective='sharded'`` the packed transports instead
run the decode-once pass shard-locally over each device's K_local
clients and finish with ONE f32 psum of the n-coordinate partials
(``kernels.ops.spfl_aggregate_packed_sharded``): per leaf the only
cross-device traffic is n floats (plus n int32 vote partials on the
flat path) instead of K*W payload words.  Integer state (votes, CRC
folds, flip counts) is bit-exact vs the gathered path — the bit
channel's counter PRF addresses global bit indices, so even the
corrupted buffers are identical — and the f32 aggregate differs only
by the documented few-ulp partial-sum reassociation.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.adversary import clients as adv_clients
from repro.adversary import screen as adv_screen
from repro.configs.base import FLConfig
from repro.core import bitchannel
from repro.core import channel as chan
from repro.core.quantize import (
    QuantizedGradient, dequantize_modulus, packet_bits,
    quantization_error_bound, stochastic_quantize,
)
from repro.kernels import ops as kops
from repro.obs.record import RoundTelemetry
from repro.obs.trace import stage_scope
from repro.wire import corrupt as wire_corrupt
from repro.wire import format as wire_fmt
from repro.wire import packets as wire_packets
from repro.wire import vote as wire_vote

Array = jax.Array

KINDS = ('spfl', 'spfl_retx', 'dds', 'onebit', 'scheduling', 'error_free')
_Q_FLOOR = 1e-8        # below this, 1/q unbiasing is switched off (q ~ 0)

# Every transport returns the structured per-round telemetry record
# (repro.obs.record.RoundTelemetry).  It absorbed the grab-bag
# ``TransportDiagnostics`` NamedTuple that used to live here — same
# leading fields, same None-off-path contract — and additionally carries
# the allocation state the training loops attach via
# ``RoundTelemetry.with_allocation`` before ring-buffering the record on
# device (repro.obs.ringbuf).


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def single_packet_success_prob(beta, p_w, gain, n_bits, fl: FLConfig):
    """Success probability for baselines that send ONE packet over the
    client's whole band at full power.  Uses the paper's H convention
    (channel.h_term) with the band-split factor removed, i.e. exponent
    n_bits/(beta*B*tau) instead of 2*n_bits/(beta*B*tau)."""
    h = chan.h_term(beta, p_w, gain, n_bits / 2.0, fl)
    return jnp.exp(h)


def _per_client_quantize(grads: Array, bits: int, key) -> QuantizedGradient:
    """grads: (K, l) -> per-client-range quantization."""
    a = jnp.abs(grads)
    g_min = jnp.min(a, axis=1, keepdims=True)
    g_max = jnp.max(a, axis=1, keepdims=True)
    return stochastic_quantize(grads, bits, key, g_min, g_max)


def _inverse_prob(accept: Array, q: Array) -> Array:
    """accept/q with the q->0 guard (accept ~ Bernoulli(q))."""
    safe = jnp.maximum(q, _Q_FLOOR)
    return jnp.where(q > _Q_FLOOR, accept.astype(jnp.float32) / safe, 0.0)


def _seq_client_mean(vals: Array) -> Array:
    """Mean over the leading client axis by *sequential* accumulation.

    The decode-once kernel sums clients over a sequential grid dimension
    (k = 0, 1, ..., K-1); f32 addition is order-sensitive, so the FLAT
    analytic paths associate the same way to keep the packed-vs-analytic
    difference down to the bounded FMA-contraction wobble (jnp.mean's
    tree reduction adds its own last-ulp reordering on top).

    Flat (paper-scale, unsharded) paths only: the tree transports keep
    ``jnp.sum`` so GSPMD can lower the sharded client axis to ONE
    cross-client all-reduce (see training/distributed.py) instead of a
    serial chain of per-slice gathers."""
    return _seq_client_sum(vals) / vals.shape[0]


def _seq_client_sum(vals: Array) -> Array:
    """Sequential-order client sum (see _seq_client_mean) — split out so
    the adversarial paths can divide by the *present* client count
    instead of K while keeping the same accumulation order."""
    acc = vals[0]
    for i in range(1, vals.shape[0]):
        acc = acc + vals[i]
    return acc


def _present_denom(k: int, active, suspect):
    """Aggregation denominator under dropout / screening.

    Baseline rounds divide by the static cohort size K.  Once clients
    can drop (``active``) or be screened (``suspect``), dividing by K
    would shrink the update toward zero, so the mean renormalizes over
    the *present* clients — active and not screened.  Channel erasures
    stay in the count: the 1/q weights already compensate them in
    expectation.  With neither knob in play this returns the Python int
    K (the seed paths are untouched); at full benign participation the
    f32 sum of K ones equals float(K) exactly, so a screened-but-clean
    round divides by the same f32 value as ``acc / K``.
    """
    if active is None and suspect is None:
        return k
    present = (jnp.ones((k,), jnp.float32) if active is None
               else active.astype(jnp.float32))
    if suspect is not None:
        present = present * (1.0 - suspect.astype(jnp.float32))
    return jnp.maximum(jnp.sum(present), 1.0)


# ---------------------------------------------------------------------------
# wire materialization
# ---------------------------------------------------------------------------

WIRE_KINDS = ('analytic', 'packed')
COLLECTIVE_KINDS = ('gather', 'sharded')


def _resolve_collective(collective: Optional[str], wire: str, mesh,
                        client_axes) -> Tuple[str, Optional[tuple]]:
    """Validate the collective knob: 'sharded' needs the packed wire and
    a mesh to shard over.  Returns (collective, resolved client_axes)."""
    collective = 'gather' if collective is None else collective
    assert collective in COLLECTIVE_KINDS, collective
    if collective == 'sharded':
        if wire != 'packed':
            raise ValueError("collective='sharded' requires wire='packed'")
        if mesh is None:
            raise ValueError("collective='sharded' requires a mesh "
                             "(training/distributed.py passes it through)")
        return collective, kops.resolve_client_axes(mesh, client_axes)
    return collective, None


def _client_constrain(x: Array, mesh, client_axes) -> Array:
    """Pin a leading-K array to the client-sharded layout so GSPMD hands
    the sharded collective already-local payload rows (skipped when the
    mesh cannot divide K — the shard_map pad handles raggedness)."""
    axes = client_axes if len(client_axes) > 1 else client_axes[0]
    shards = 1
    for a in client_axes:
        shards *= mesh.shape[a]
    if x.shape[0] % shards != 0:
        return x
    spec = jax.sharding.PartitionSpec(axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def encode_wire(qg: QuantizedGradient, round_idx: int = 0
                ) -> Tuple[Array, Array, int]:
    """Client side of the packed wire: encode a (K, l) quantized gradient
    into framed buffers -> (sign_words (K, Ws), mod_words (K, Wm),
    measured bits of the real buffers)."""
    K = qg.sign.shape[0]
    sign_words, mod_words = wire_packets.encode_uplink_batch(
        qg.sign, qg.qidx, qg.g_min.reshape(K), qg.g_max.reshape(K),
        bits=qg.bits, round_idx=round_idx)
    measured = wire_fmt.WORD_BITS * K * (sign_words.shape[1]
                                         + mod_words.shape[1])
    return sign_words, mod_words, measured


def decode_wire(qg: QuantizedGradient, sign_words: Array, mod_words: Array
                ) -> Tuple[QuantizedGradient, Array]:
    """PS side: decode (possibly damaged) buffers back into a
    QuantizedGradient shaped like ``qg`` -> (decoded, crc_ok flags)."""
    l = qg.sign.shape[1]
    dec = wire_packets.decode_uplink_batch(sign_words, mod_words,
                                           n=l, bits=qg.bits)
    rec = QuantizedGradient(dec.sign, dec.qidx,
                            dec.g_min.reshape(qg.g_min.shape),
                            dec.g_max.reshape(qg.g_max.shape), qg.bits)
    return rec, dec


def materialize_wire(qg: QuantizedGradient, round_idx: int = 0
                     ) -> Tuple[QuantizedGradient, int, Array]:
    """Round-trip a (K, l) quantized gradient through the packed wire —
    the retained unpack-per-client *reference* path (the live transports
    decode once via ``kernels.ops.spfl_aggregate_packed`` instead).

    Encodes every client's sign/modulus packets into framed uint32 word
    buffers (repro.wire.packets), decodes them back on the "PS side", and
    returns (reconstructed QuantizedGradient, measured payload bits of the
    real buffers, per-client checksum-ok flags).  The decode is exact:
    knob indices and the bitcast (g_min, g_max) side-channel survive
    bit-for-bit; signs come back in {-1, +1} (a 1-bit wire cannot carry
    sign 0 — see repro.wire.__doc__; the reconstruction s*Q_v is still
    exact because g=0 coordinates quantize to knob 0 with g_min=0).
    """
    sign_words, mod_words, measured = encode_wire(qg, round_idx)
    rec, dec = decode_wire(qg, sign_words, mod_words)
    return rec, measured, dec.sign_ok & dec.mod_ok


# ---------------------------------------------------------------------------
# SP-FL (flat)
# ---------------------------------------------------------------------------

def spfl_aggregate(grads: Array, gbar: Array, q: Array, p: Array,
                   bits: int, b0: int, key, n_retx: int = 0,
                   wire: str = 'analytic', round_idx=0,
                   channel: str = 'bernoulli',
                   collective: str = 'gather', mesh=None,
                   client_axes: Optional[tuple] = None,
                   attack: str = 'none', byz_mask: Optional[Array] = None,
                   attack_scale: float = 10.0,
                   active: Optional[Array] = None, screen: bool = False,
                   screen_z: float = 4.0, min_participation: float = 0.0
                   ) -> Tuple[Array, RoundTelemetry]:
    """Eq. (15)-(17).  grads: (K, l); gbar: (l,) or (K, l); q, p: (K,).

    ``wire='packed'`` materializes the two packets as bit-packed word
    buffers and decodes from them; the aggregate is identical and
    ``payload_bits`` is the measured buffer size.  ``round_idx`` stamps
    the packet headers (PS-side attribution).

    ``channel='bitlevel'`` (requires ``wire='packed'``) replaces the
    per-packet Bernoulli draw with per-bit flips of the materialized
    buffers at a BER calibrated to the same (q, p): ``sign_ok``/``mod_ok``
    come from the PS-side xor-fold verification of the corrupted buffers,
    failed sign packets are *resent as real buffers* (same payload, fresh
    header stamp, fresh channel draw) up to ``n_retx`` times, and the
    measured resend bits land in ``payload_bits``.

    ``collective='sharded'`` (packed wire + ``mesh``) keeps every
    (K, W)-shaped pass shard-local over the mesh's client axes — the
    decode-once aggregation becomes per-device partials + one psum, the
    bit channel corrupts and CRC-folds each shard's own rows — so no
    client payload is ever all-gathered (see the module docstring for
    the exactness contract vs 'gather').

    Adversarial cohort (repro.adversary): ``attack`` in ``ATTACK_KINDS``
    with ``byz_mask`` (K,) bool applies the attacker transform at the
    wire level — ``'signflip'`` XORs the framed packed sign payload (CRC
    patched, so the forged frame verifies) or negates the analytic sign
    matrix; ``'scaled'`` inflates the reported range scalars by
    ``attack_scale``; ``'labelflip'`` is data poisoning upstream, a
    transport no-op.  ``active`` (K,) bool marks straggler/dropout rows:
    they transmit nothing (sign_ok/mod_ok forced False -> zero-weight
    rows in the kernel) and the mean renormalizes over the present
    count.  ``screen=True`` gates each client's weight by the
    packed-domain suspicion verdict (sign-vote disagreement + robust
    norm z-score, ``screen_z`` threshold); ``min_participation`` is the
    graceful-degradation floor — when fewer than ceil(m * K) modulus
    packets survive, ALL rows fall back to sign-only reuse (gbar
    compensation), the paper's own degradation mode.
    """
    assert wire in WIRE_KINDS, wire
    assert channel in chan.CHANNEL_KINDS, channel
    if channel == 'bitlevel' and wire != 'packed':
        raise ValueError("channel='bitlevel' requires wire='packed'")
    collective, client_axes = _resolve_collective(collective, wire, mesh,
                                                  client_axes)
    sharded = collective == 'sharded'
    assert attack in adv_clients.ATTACK_KINDS, attack
    K, l = grads.shape
    kq, ko = jax.random.split(key)
    with stage_scope('quantize_pack'):
        qg = _per_client_quantize(grads, bits, kq)
    if attack == 'scaled' and byz_mask is not None:
        qg = adv_clients.scale_ranges(qg, byz_mask, attack_scale)
    elif attack == 'signflip' and byz_mask is not None and wire != 'packed':
        qg = adv_clients.flip_signs(qg, byz_mask)
    q_eff = 1.0 - (1.0 - q) ** (n_retx + 1)      # sign retransmission(s)

    extras = {}
    sign_words = mod_words = None
    if wire == 'packed':
        with stage_scope('quantize_pack'):
            sign_words, mod_words, measured = encode_wire(qg, round_idx)
        if attack == 'signflip' and byz_mask is not None:
            # packed-domain attack, pre-transmit: the forged frame's CRC
            # covers the lie, so the channel/PS treat it as pristine
            sign_words = adv_clients.signflip_frames(sign_words,
                                                     byz_mask, l)
        if sharded:
            sign_words = _client_constrain(sign_words, mesh, client_axes)
            mod_words = _client_constrain(mod_words, mesh, client_axes)
    if channel == 'bitlevel':
        with stage_scope('corrupt_fold'):
            rep = bitchannel.transmit_uplink(
                ko, sign_words, mod_words, q, p, n=l, bits=bits,
                n_retx=n_retx, mesh=mesh if sharded else None,
                client_axes=client_axes)
        sign_words, mod_words = rep.sign_words, rep.mod_words
        sign_ok, mod_ok = rep.sign_ok, rep.mod_ok
        retx = jnp.sum(rep.retx_attempts).astype(jnp.float32)
        payload = float(measured) + rep.retx_bits
        extras = dict(sign_flips=rep.sign_flips, mod_flips=rep.mod_flips,
                      sign_crc_ok=rep.sign_crc_ok, mod_crc_ok=rep.mod_crc_ok,
                      retx_attempts=rep.retx_attempts)
    else:
        if wire == 'packed':
            sign_bits = wire_fmt.WORD_BITS * wire_fmt.sign_packet_words(l)
            payload_base = float(measured)
        else:
            sign_bits, mod_bits = packet_bits(l, bits, b0)
            payload_base = float(K * (sign_bits + mod_bits))
        if n_retx == 0:
            sign_ok, mod_ok = chan.simulate_outcomes(ko, q_eff, p)
            retx = jnp.zeros((), jnp.float32)
        else:
            ks, km = jax.random.split(ko)
            sign_ok, retx_k = chan.simulate_attempts(ks, q, n_retx)
            mod_ok = jax.random.uniform(km, p.shape) < p
            retx = jnp.sum(retx_k).astype(jnp.float32)
            extras = dict(retx_attempts=retx_k)
        payload = payload_base + retx * sign_bits

    if active is not None:           # stragglers/dropouts transmit nothing
        sign_ok = sign_ok & active
        mod_ok = mod_ok & active
        extras['active'] = active
    if min_participation > 0.0:
        # graceful degradation: too few surviving modulus packets ->
        # sign-only reuse for the whole cohort (paper's fallback mode)
        floor = int(math.ceil(min_participation * K))
        n_mod = jnp.sum(mod_ok.astype(jnp.int32))
        mod_ok = jnp.where(n_mod >= floor, mod_ok, jnp.zeros_like(mod_ok))

    w = _inverse_prob(sign_ok, q_eff)
    suspect = None
    if screen:
        with stage_scope('screen'):
            if wire == 'packed':
                rows = wire_packets.sign_payload(sign_words)
                maj = wire_vote.majority_words(rows, sign_ok, l)
                dis = wire_vote.disagreement(rows, maj, l)
                _, hdr_gmax = wire_packets.mod_header_ranges(mod_words)
                gate, suspect, suspicion = adv_screen.screen_gate(
                    hdr_gmax, mod_ok, dis, l, sign_ok, screen_z)
            else:
                gate, suspect, suspicion = adv_screen.screen_gate(
                    qg.g_max, mod_ok, z_thresh=screen_z)
            w = w * gate             # screening = weighting: 0-rows are
        extras['suspect'] = suspect  # bit-exact no-ops in the kernel
        extras['suspicion'] = suspicion
    with stage_scope('decode_aggregate'):
        if wire == 'packed':
            # decode-once: O(K) header words, then ONE fused kernel pass
            # over the K stacked payload buffers — no per-client unpack,
            # no (K, l) float intermediate (kernels.ops.
            # spfl_aggregate_packed); under 'sharded' the pass is
            # per-device partials + one psum instead
            g_min, g_max = wire_packets.mod_header_ranges(mod_words)
            if sharded:
                acc, votes = kops.spfl_aggregate_packed_sharded(
                    wire_packets.sign_payload(sign_words),
                    wire_packets.mod_payload(mod_words),
                    jnp.asarray(gbar, jnp.float32), g_min, g_max, mod_ok,
                    w, sign_ok, l, bits, mesh=mesh,
                    client_axes=client_axes)
            else:
                acc, votes = kops.spfl_aggregate_packed(
                    wire_packets.sign_payload(sign_words),
                    wire_packets.mod_payload(mod_words),
                    jnp.asarray(gbar, jnp.float32), g_min, g_max, mod_ok,
                    w, sign_ok, l, bits)
            ghat = acc / _present_denom(K, active, suspect)
            if votes is not None:
                extras['sign_votes'] = votes
        else:
            modulus = dequantize_modulus(qg)                   # (K, l)
            gbar_k = (jnp.broadcast_to(gbar, grads.shape)
                      if gbar.ndim == 1 else gbar)
            modulus = jnp.where(mod_ok[:, None], modulus, gbar_k)
            signed = qg.sign.astype(jnp.float32) * modulus
            ghat = (_seq_client_sum(w[:, None] * signed)
                    / _present_denom(K, active, suspect))

    return ghat, RoundTelemetry(sign_ok, mod_ok, sign_ok,
                                      jnp.asarray(payload, jnp.float32),
                                      retx, **extras)


# ---------------------------------------------------------------------------
# baselines (flat)
# ---------------------------------------------------------------------------

def _baseline_packet_fate(key, q: Array, n_bits: int, fl: FLConfig
                          ) -> Array:
    """One success draw per client for the single-packet baselines.

    ``fl.channel='bernoulli'`` draws straight from the analytic q;
    'bitlevel' first routes q through the shared bit-channel calibration
    (``bitchannel.calibrated_success_prob`` for a virtual packet of
    ``n_bits``) and draws through the shared attempt machinery — the
    payload stays analytic (nothing materialized), but the packet fate
    now carries the same calibration floors as the materialized spfl
    transports, making cross-framework bitlevel comparisons
    apples-to-apples."""
    if fl.channel == 'bitlevel':
        q = bitchannel.calibrated_success_prob(q, n_bits)
        ok, _ = chan.simulate_attempts(key, q, 0)
        return ok
    return jax.random.uniform(key, jnp.shape(q)) < q


def dds_aggregate(grads: Array, beta: Array, gains: Array, p_w: Array,
                  fl: FLConfig, key) -> Tuple[Array, RoundTelemetry]:
    """[29]: one packet of l(b+1)+b0 bits; failures discarded; mean over
    the received set."""
    K, l = grads.shape
    kq, ko = jax.random.split(key)
    qg = _per_client_quantize(grads, fl.quant_bits, kq)
    n_bits = l * (fl.quant_bits + 1) + fl.b0_bits
    q = single_packet_success_prob(beta, p_w, gains, n_bits, fl)
    ok = _baseline_packet_fate(ko, q, n_bits, fl)
    vals = qg.sign.astype(jnp.float32) * dequantize_modulus(qg)
    denom = jnp.maximum(jnp.sum(ok.astype(jnp.float32)), 1.0)
    ghat = jnp.sum(jnp.where(ok[:, None], vals, 0.0), axis=0) / denom
    payload = jnp.asarray(K * n_bits, jnp.float32)
    return ghat, RoundTelemetry(ok, ok, ok, payload, jnp.zeros(()))


def onebit_aggregate(grads: Array, beta: Array, gains: Array, p_w: Array,
                     fl: FLConfig, key) -> Tuple[Array, RoundTelemetry]:
    """[28]: sign-only uplink.  The aggregate is the mean received sign
    scaled by the mean client modulus (one extra scalar per client,
    analogous to the b0 side-channel) so the step magnitude is comparable
    with modulus-carrying schemes."""
    K, l = grads.shape
    q = single_packet_success_prob(beta, p_w, gains, float(l), fl)
    ok = _baseline_packet_fate(key, q, l, fl)
    scale = jnp.mean(jnp.abs(grads), axis=1, keepdims=True)    # (K, 1)
    vals = jnp.sign(grads) * scale
    denom = jnp.maximum(jnp.sum(ok.astype(jnp.float32)), 1.0)
    ghat = jnp.sum(jnp.where(ok[:, None], vals, 0.0), axis=0) / denom
    payload = jnp.asarray(K * l, jnp.float32)
    return ghat, RoundTelemetry(ok, jnp.zeros_like(ok), ok, payload,
                                      jnp.zeros(()))


def scheduling_aggregate(grads: Array, gains: Array, p_w: Array,
                         fl: FLConfig, key,
                         ratio: Optional[float] = None
                         ) -> Tuple[Array, RoundTelemetry]:
    """[46]: PS schedules the ceil(ratio*K) devices with the largest
    instantaneous channel gain; each gets an equal share of the band."""
    K, l = grads.shape
    ratio = fl.scheduling_ratio if ratio is None else ratio
    m = max(1, math.ceil(ratio * K))
    kh, ko, kq = jax.random.split(key, 3)
    h2 = jax.random.exponential(kh, (K,))           # Rayleigh |h|^2
    inst = h2 * gains
    thresh = jnp.sort(inst)[K - m]
    sched = inst >= thresh
    beta = jnp.where(sched, 1.0 / m, 1e-9)
    qg = _per_client_quantize(grads, fl.quant_bits, kq)
    n_bits = l * (fl.quant_bits + 1) + fl.b0_bits
    q = single_packet_success_prob(beta, p_w, gains, n_bits, fl)
    ok = _baseline_packet_fate(ko, q, n_bits, fl) & sched
    vals = qg.sign.astype(jnp.float32) * dequantize_modulus(qg)
    denom = jnp.maximum(jnp.sum(ok.astype(jnp.float32)), 1.0)
    ghat = jnp.sum(jnp.where(ok[:, None], vals, 0.0), axis=0) / denom
    payload = jnp.asarray(m * n_bits, jnp.float32)
    return ghat, RoundTelemetry(ok, ok, ok, payload, jnp.zeros(()))


def error_free_aggregate(grads: Array, fl: FLConfig, key,
                         wire: Optional[str] = None, round_idx=0,
                         collective: Optional[str] = None, mesh=None,
                         client_axes: Optional[tuple] = None
                         ) -> Tuple[Array, RoundTelemetry]:
    wire = fl.wire if wire is None else wire
    assert wire in WIRE_KINDS, wire
    collective, client_axes = _resolve_collective(
        fl.collective if collective is None else collective, wire, mesh,
        client_axes)
    K, l = grads.shape
    qg = _per_client_quantize(grads, fl.quant_bits, key)
    ok = jnp.ones((K,), bool)
    extras = {}
    if wire == 'packed':
        sign_words, mod_words, measured = encode_wire(qg, round_idx)
        payload = jnp.asarray(measured, jnp.float32)
        ones = jnp.ones((K,), jnp.float32)
        g_min, g_max = wire_packets.mod_header_ranges(mod_words)
        if collective == 'sharded':
            acc, votes = kops.spfl_aggregate_packed_sharded(
                _client_constrain(wire_packets.sign_payload(sign_words),
                                  mesh, client_axes),
                _client_constrain(wire_packets.mod_payload(mod_words),
                                  mesh, client_axes),
                jnp.zeros((l,), jnp.float32), g_min, g_max, ones, ones,
                ok, l, fl.quant_bits, mesh=mesh, client_axes=client_axes)
        else:
            acc, votes = kops.spfl_aggregate_packed(
                wire_packets.sign_payload(sign_words),
                wire_packets.mod_payload(mod_words),
                jnp.zeros((l,), jnp.float32), g_min, g_max, ones, ones,
                ok, l, fl.quant_bits)
        ghat = acc / K
        if votes is not None:
            extras['sign_votes'] = votes
    else:
        payload = jnp.asarray(K * (l * (fl.quant_bits + 1) + fl.b0_bits),
                              jnp.float32)
        ghat = _seq_client_mean(qg.sign.astype(jnp.float32)
                                * dequantize_modulus(qg))
    return ghat, RoundTelemetry(ok, ok, ok, payload, jnp.zeros(()),
                                      **extras)


# ---------------------------------------------------------------------------
# pytree variants (LLM-scale): one radio per client, leaf-wise math
# ---------------------------------------------------------------------------

def tree_client_stats(grads_tree) -> dict:
    """Per-client (leading-K) scalars across the whole gradient pytree:
    ||g_k||^2, min|g|, max|g|, dim."""
    leaves = jax.tree.leaves(grads_tree)
    K = leaves[0].shape[0]
    g2 = sum(jnp.sum(lf.astype(jnp.float32).reshape(K, -1) ** 2, axis=1)
             for lf in leaves)
    g_min = jnp.full((K,), jnp.inf)
    g_max = jnp.zeros((K,))
    for lf in leaves:
        a = jnp.abs(lf.astype(jnp.float32)).reshape(K, -1)
        g_min = jnp.minimum(g_min, jnp.min(a, axis=1))
        g_max = jnp.maximum(g_max, jnp.max(a, axis=1))
    dim = sum(int(lf.size) // K for lf in leaves)
    return {'g2': g2, 'g_min': g_min, 'g_max': g_max, 'dim': dim}


def _bitlevel_tree_pass(key, word_leaves, ber, frame_words: int, k: int,
                        mesh=None, client_axes=None):
    """One transmission of every client's *virtual* framed packet whose
    payload words are scattered across per-leaf buffers (K, W_i).

    Corrupts each leaf buffer plus one draw for the per-client framing
    words (header + crc, which the tree path never materializes), and
    verifies by folding the flip masks: on a contiguous buffer the
    PS-side check ``fold(received[:-1]) == received[-1]`` is equivalent
    to ``fold(flip mask over ALL words incl. crc) == 0``, so
    accumulating the mask fold across leaves computes exactly the
    xor-fold verification the flat path runs on real buffers.

    ``mesh`` keeps each leaf's corruption shard-local (same bits — the
    counter PRF is globally indexed); the (K, frame_words) framing draw
    stays unsharded, it is O(K) words.

    Returns (corrupted leaf buffers, verify_ok (K,), flips (K,)).
    """
    fold = jnp.zeros((k,), jnp.uint32)
    flips = jnp.zeros((k,), jnp.int32)
    rx = []
    for i, wl in enumerate(word_leaves):
        # fused corrupt + mask-fold + popcount in one pass (the Pallas
        # corruption kernel on TPU, its bit-identical jnp twin elsewhere)
        cw, f, nf = kops.corrupt_fold_words(
            jax.random.fold_in(key, i), wl, ber, mesh=mesh,
            client_axes=client_axes)
        rx.append(cw)
        fold = fold ^ f
        flips = flips + nf
    fmask = wire_corrupt.flip_mask(
        jax.random.fold_in(key, len(word_leaves)), (k, frame_words), ber)
    fold = fold ^ wire_fmt.xor_fold(fmask)
    flips = flips + wire_corrupt.count_flips(fmask)
    return rx, fold == 0, flips


def spfl_aggregate_tree(grads_tree, gbar_tree, q: Array, p: Array,
                        fl: FLConfig, key, stats: Optional[dict] = None,
                        n_retx: int = 0, wire: Optional[str] = None,
                        channel: Optional[str] = None,
                        collective: Optional[str] = None, mesh=None,
                        client_axes: Optional[tuple] = None,
                        round_idx=None, attack: str = 'none',
                        byz_mask: Optional[Array] = None,
                        attack_scale: float = 10.0,
                        active: Optional[Array] = None,
                        screen: bool = False, screen_z: float = 4.0,
                        min_participation: float = 0.0):
    """SP-FL over per-client gradient pytrees (leaves (K, ...)).

    The quantizer range, the packet outcomes and the 1/q weights are
    per-client and shared across leaves; everything else is the flat math
    applied leaf-wise.  Returns (ghat_tree, stats, diagnostics).

    ``wire='packed'`` (default: ``fl.wire``) bit-packs each leaf's sign
    and knob payloads into wire words and aggregates straight from them:
    the cross-client reduce per leaf is one decode-once kernel launch
    over the (K, W) word buffers (``kernels.ops.spfl_aggregate_packed``)
    — no per-client unpack, no (K, d) float intermediate, and the
    ``uplink_reduce_dtype`` knob is subsumed (packed words are 4x
    narrower than bf16 at b=3).  At mesh scale the gathered kernel wants
    the full (K, W) buffers on one device, so a sharded client axis gets
    all-gathered; ``collective='sharded'`` (default ``fl.collective``,
    needs ``mesh``) runs each leaf's decode-once pass shard-locally and
    finishes with one n-float psum of the partials instead — the
    analytic path keeps a jnp.sum reduce, which already lowers to one
    all-reduce.  The per-client framing (headers + b0
    range + checksums) is one packet pair per client per round
    regardless of leaf count, so the measured ``payload_bits`` charges
    it once per client.

    ``channel='bitlevel'`` (default: ``fl.channel``; requires the packed
    wire) flips bits of the leaf word buffers at the (q, p)-calibrated
    BER and drives ``sign_ok``/``mod_ok`` from the xor-fold verification
    of the flipped words — one virtual packet pair per client spanning
    all leaves, with sign retransmissions re-sending the same payload
    under a fresh channel draw (the fresh header stamp lives in the
    framing words, which the tree path draws but does not materialize).

    ``round_idx`` (optional, traced scalar OK) stamps the round into the
    transmission PRNG stream — the tree path materializes no headers, so
    the round enters through the key instead of the framing words.  A
    scanned multi-round body can therefore hold one key and pass the
    traced round index, mirroring the flat path's traced-header stamp.
    ``None`` (default) leaves the key untouched, preserving the exact
    draws of every existing caller.

    Adversarial knobs mirror ``spfl_aggregate``: ``'signflip'`` negates
    the byzantine rows' sign matrix *before* packing (the encoder then
    stamps a CRC over the forged payload — same end state as the flat
    path's framed XOR); ``'scaled'`` inflates the per-client range
    *reports* fed to the decode kernels while quantizing honestly;
    ``active`` rows are zeroed out and the per-leaf mean renormalizes;
    ``screen=True`` applies the norm-report robust z-gate only (the tree
    path discards votes, so vote screening stays a flat-wire feature).
    """
    wire = fl.wire if wire is None else wire
    channel = fl.channel if channel is None else channel
    assert wire in WIRE_KINDS, wire
    assert channel in chan.CHANNEL_KINDS, channel
    if channel == 'bitlevel' and wire != 'packed':
        raise ValueError("channel='bitlevel' requires wire='packed'")
    collective, client_axes = _resolve_collective(
        fl.collective if collective is None else collective, wire, mesh,
        client_axes)
    sharded = collective == 'sharded'
    if stats is None:
        stats = tree_client_stats(grads_tree)
    K = q.shape[0]
    if round_idx is not None:
        key = jax.random.fold_in(key, round_idx)
    kq, ko = jax.random.split(key)
    q_eff = 1.0 - (1.0 - q) ** (n_retx + 1)

    g_min, g_max = stats['g_min'], stats['g_max']
    assert attack in adv_clients.ATTACK_KINDS, attack
    byz = byz_mask if attack in ('signflip', 'scaled') else None
    g_min_rep, g_max_rep = g_min, g_max      # range *reports* (the lie)
    if attack == 'scaled' and byz is not None:
        s = jnp.float32(attack_scale)
        g_min_rep = jnp.where(byz, g_min * s, g_min)
        g_max_rep = jnp.where(byz, g_max * s, g_max)
    bits = fl.quant_bits
    # beyond-paper §Perf (analytic wire only — the packed wire reduces
    # packed words, narrower than any float dtype): the payload is
    # already b-bit quantized, so the cross-client reduction can run in
    # bf16, halving uplink bytes
    rdt = jnp.bfloat16 if fl.uplink_reduce_dtype == 'bfloat16' \
        else jnp.float32

    leaves, treedef = jax.tree.flatten(grads_tree)
    gbar_leaves = jax.tree.leaves(gbar_tree)
    keys = jax.random.split(kq, len(leaves))

    # ---- clients: quantize every leaf (+ pack on the packed wire) ----
    with stage_scope('quantize_pack'):
        qgs, sws, qws = [], [], []
        payload_words = 0
        for lf, lkey in zip(leaves, keys):
            Kd = lf.shape[0]
            flat = lf.astype(jnp.float32).reshape(Kd, -1)
            qg = stochastic_quantize(flat, bits, lkey,
                                     g_min[:, None], g_max[:, None])
            if attack == 'signflip' and byz is not None:
                qg = adv_clients.flip_signs(qg, byz)
            if attack == 'scaled' and byz is not None:
                # the analytic dequant must see the scaled *report*
                qg = qg._replace(g_min=g_min_rep[:, None],
                                 g_max=g_max_rep[:, None])
            qgs.append(qg)
            if wire == 'packed':
                sw = wire_fmt.pack_bits_ref(wire_fmt.sign_to_bits(qg.sign), 1)
                qw = wire_fmt.pack_bits_ref(qg.qidx, bits)
                if sharded:
                    sw = _client_constrain(sw, mesh, client_axes)
                    qw = _client_constrain(qw, mesh, client_axes)
                sws.append(sw)
                qws.append(qw)
                payload_words += sws[-1].shape[-1] + qws[-1].shape[-1]

    # ---- channel: packet fate (and, bit-level, payload damage) ----
    extras = {}
    shard_kw = dict(mesh=mesh if sharded else None,
                    client_axes=client_axes)
    if channel == 'bitlevel':
        with stage_scope('corrupt_fold'):
            sign_frame = wire_fmt.SIGN_HEADER_WORDS + wire_fmt.CRC_WORDS
            mod_frame = wire_fmt.MOD_HEADER_WORDS + wire_fmt.CRC_WORDS
            ws = sum(sw.shape[-1] for sw in sws) + sign_frame
            wm = sum(qw.shape[-1] for qw in qws) + mod_frame
            ber_s = bitchannel.ber_for_success(q, ws)
            ber_v = bitchannel.ber_for_success(p, wm)
            ks, kv = jax.random.split(ko)
            qws, mod_ok, mod_flips = _bitlevel_tree_pass(
                kv, qws, ber_v, mod_frame, K, **shard_kw)
            # pristine payloads: retransmissions resend these
            orig_sws = sws
            sws, sign_ok, sign_flips = _bitlevel_tree_pass(
                ks, sws, ber_s, sign_frame, K, **shard_kw)
            sign_crc_ok = sign_ok
            retx_k = jnp.zeros((K,), jnp.int32)
            for attempt in range(1, n_retx + 1):
                failed = ~sign_ok
                rx_a, ok_a, flips_a = _bitlevel_tree_pass(
                    jax.random.fold_in(ks, attempt), orig_sws, ber_s,
                    sign_frame, K, **shard_kw)
                rescued = failed & ok_a
                sws = [jnp.where(rescued[:, None], a, r)
                       for a, r in zip(rx_a, sws)]
                sign_flips = sign_flips + jnp.where(failed, flips_a, 0)
                retx_k = retx_k + failed.astype(jnp.int32)
                sign_ok = sign_ok | rescued
            retx = jnp.sum(retx_k).astype(jnp.float32)
            extras = dict(sign_flips=sign_flips, mod_flips=mod_flips,
                          sign_crc_ok=sign_crc_ok, mod_crc_ok=mod_ok,
                          retx_attempts=retx_k)
    elif n_retx == 0:
        sign_ok, mod_ok = chan.simulate_outcomes(ko, q_eff, p)
        retx = jnp.zeros((), jnp.float32)
    else:
        ks, km = jax.random.split(ko)
        sign_ok, retx_k = chan.simulate_attempts(ks, q, n_retx)
        mod_ok = jax.random.uniform(km, p.shape) < p
        retx = jnp.sum(retx_k).astype(jnp.float32)
        extras = dict(retx_attempts=retx_k)

    if active is not None:           # stragglers/dropouts transmit nothing
        sign_ok = sign_ok & active
        mod_ok = mod_ok & active
        extras['active'] = active
    if min_participation > 0.0:
        floor = int(math.ceil(min_participation * K))
        n_mod = jnp.sum(mod_ok.astype(jnp.int32))
        mod_ok = jnp.where(n_mod >= floor, mod_ok, jnp.zeros_like(mod_ok))
    w = _inverse_prob(sign_ok, q_eff)
    suspect = None
    if screen:
        # tree path: norm-report screening only (votes are discarded at
        # LLM scale — see the docstring)
        with stage_scope('screen'):
            gate, suspect, suspicion = adv_screen.screen_gate(
                g_max_rep, mod_ok, z_thresh=screen_z)
            w = w * gate
        extras['suspect'] = suspect
        extras['suspicion'] = suspicion
    denom = _present_denom(K, active, suspect)

    # ---- PS: decode-once aggregate per leaf ----
    with stage_scope('decode_aggregate'):
        out = []
        for i, (lf, gbar_leaf) in enumerate(zip(leaves, gbar_leaves)):
            qg = qgs[i]
            shape = lf.shape
            Kd = shape[0]
            gb = gbar_leaf.astype(jnp.float32)
            per_client_gb = gb.shape == shape           # last_local vs shared
            if wire == 'packed':
                # the cross-client collective consumes the packed (K, W)
                # payload words directly: one fused unpack->dequant->weight->
                # accumulate kernel launch per leaf, no K unpack passes and
                # no (K, d) float intermediate (the bf16 reduce is subsumed —
                # the packed words are 4x narrower than bf16 at b=3); under
                # 'sharded' each device accumulates its local clients and
                # ONE d-float psum finishes the leaf (no vote psum: the tree
                # path discards votes, so the partial traffic stays d floats)
                d = qg.sign.shape[-1]
                gb_leaf = (gb.reshape(Kd, -1) if per_client_gb
                           else gb.reshape(-1))
                if sharded:
                    acc, _ = kops.spfl_aggregate_packed_sharded(
                        sws[i], qws[i], gb_leaf, g_min_rep, g_max_rep,
                        mod_ok, w, sign_ok, d, bits, mesh=mesh,
                        client_axes=client_axes, with_votes=False)
                else:
                    acc, _ = kops.spfl_aggregate_packed(
                        sws[i], qws[i], gb_leaf,
                        g_min_rep, g_max_rep, mod_ok, w, sign_ok, d, bits)
                out.append((acc / denom).reshape(shape[1:]))
                continue
            modulus = dequantize_modulus(qg)
            if per_client_gb:
                gb = gb.reshape(Kd, -1)
            else:
                gb = jnp.broadcast_to(gb.reshape(1, -1), modulus.shape)
            modulus = jnp.where(mod_ok[:, None], modulus, gb)
            signed = qg.sign.astype(jnp.float32) * modulus
            contrib = (w[:, None] * signed).astype(rdt)
            # keep the reduction itself (-> cross-client all-reduce) in rdt,
            # and as a parallel jnp.sum: the client axis is mesh-sharded at
            # LLM scale and must lower to ONE all-reduce
            out.append((jnp.sum(contrib, axis=0) / denom).astype(
                jnp.float32).reshape(shape[1:]))
        ghat = jax.tree.unflatten(treedef, out)

    l = stats['dim']
    if wire == 'packed':
        framing = (wire_fmt.SIGN_HEADER_WORDS + wire_fmt.MOD_HEADER_WORDS
                   + 2 * wire_fmt.CRC_WORDS)
        payload = K * wire_fmt.WORD_BITS * (payload_words + framing)
        sign_bits = wire_fmt.WORD_BITS * (
            sum(sw.shape[-1] for sw in sws) + wire_fmt.SIGN_HEADER_WORDS
            + wire_fmt.CRC_WORDS) if sws else 0
    else:
        sign_bits, mod_bits = packet_bits(l, bits, fl.b0_bits)
        payload = K * (sign_bits + mod_bits)
    diag = RoundTelemetry(
        sign_ok, mod_ok, sign_ok,
        jnp.asarray(float(payload) + retx * sign_bits, jnp.float32),
        retx, **extras)
    return ghat, stats, diag


def error_free_aggregate_tree(grads_tree, fl: FLConfig, key,
                              stats: Optional[dict] = None,
                              wire: Optional[str] = None,
                              collective: Optional[str] = None, mesh=None,
                              client_axes: Optional[tuple] = None,
                              round_idx=None):
    """Quantized-but-lossless tree aggregation (arctic-480b fallback and
    the error-free baseline at LLM scale).  ``round_idx`` stamps the
    round into the quantizer PRNG stream (traced scalar OK, as on
    ``spfl_aggregate_tree``); ``None`` keeps existing draws."""
    wire = fl.wire if wire is None else wire
    assert wire in WIRE_KINDS, wire
    collective, client_axes = _resolve_collective(
        fl.collective if collective is None else collective, wire, mesh,
        client_axes)
    sharded = collective == 'sharded'
    if stats is None:
        stats = tree_client_stats(grads_tree)
    g_min, g_max = stats['g_min'], stats['g_max']
    bits = fl.quant_bits
    if round_idx is not None:
        key = jax.random.fold_in(key, round_idx)
    leaves, treedef = jax.tree.flatten(grads_tree)
    keys = jax.random.split(key, len(leaves))
    K = leaves[0].shape[0]
    payload_words = [0]
    ones = jnp.ones((K,), jnp.float32)

    def leaf(gleaf, lkey):
        Kd = gleaf.shape[0]
        flat = gleaf.astype(jnp.float32).reshape(Kd, -1)
        qg = stochastic_quantize(flat, bits, lkey,
                                 g_min[:, None], g_max[:, None])
        if wire == 'packed':
            # packed collective + decode-once kernel, as in the spfl tree
            d = flat.shape[-1]
            sw = wire_fmt.pack_bits_ref(wire_fmt.sign_to_bits(qg.sign), 1)
            qw = wire_fmt.pack_bits_ref(qg.qidx, bits)
            payload_words[0] += sw.shape[-1] + qw.shape[-1]
            if sharded:
                acc, _ = kops.spfl_aggregate_packed_sharded(
                    _client_constrain(sw, mesh, client_axes),
                    _client_constrain(qw, mesh, client_axes),
                    jnp.zeros((d,), jnp.float32), g_min, g_max,
                    ones, ones, ones, d, bits, mesh=mesh,
                    client_axes=client_axes, with_votes=False)
            else:
                acc, _ = kops.spfl_aggregate_packed(
                    sw, qw, jnp.zeros((d,), jnp.float32), g_min, g_max,
                    ones, ones, ones, d, bits)
            return (acc / Kd).reshape(gleaf.shape[1:])
        signed = qg.sign.astype(jnp.float32) * dequantize_modulus(qg)
        # parallel reduce: sharded client axis -> one all-reduce
        return jnp.mean(signed, axis=0).reshape(gleaf.shape[1:])

    out = [leaf(lf, k) for lf, k in zip(leaves, keys)]
    if wire == 'packed':
        payload = K * wire_fmt.WORD_BITS * (
            payload_words[0] + wire_fmt.SIGN_HEADER_WORDS
            + wire_fmt.MOD_HEADER_WORDS + 2 * wire_fmt.CRC_WORDS)
    else:
        payload = K * (stats['dim'] * (bits + 1) + fl.b0_bits)
    ok = jnp.ones((K,), bool)
    diag = RoundTelemetry(ok, ok, ok,
                                jnp.asarray(payload, jnp.float32),
                                jnp.zeros(()))
    return jax.tree.unflatten(treedef, out), stats, diag


def delta_sq_tree(stats: dict, bits: int) -> Array:
    """Per-client quantization error bound delta^2 (Lemma 2) from stats."""
    return quantization_error_bound(stats['g_min'], stats['g_max'],
                                    stats['dim'], bits)
