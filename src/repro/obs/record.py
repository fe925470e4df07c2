"""RoundTelemetry — the typed per-round record of the SP-FL stack.

One NamedTuple (hence one pytree node) carries everything the paper's
analysis reasons about per round: packet fate (sign/modulus CRC verdicts,
eq. (11)/(13) outcomes), measured uplink bits, materialized sign
retransmissions, bit-channel damage (per-client flip counts, first-attempt
CRC state), packed-domain sign votes, and — once the training loop
enriches the record — the round's allocation state (q, p, eq. (28)
objective) and its index.

This record *absorbs and retires* ``TransportDiagnostics``: the transport
functions (``repro.core.transport``) return it directly, with the
trailing channel-specific fields ``None`` off the paths that measure them
(exactly the old contract, so field access is unchanged downstream).

Being a NamedTuple of device arrays it is a pytree: it flows through
jitted round steps, stacks into the on-device ring buffer
(``repro.obs.ringbuf``), and crosses to the host only at flush time —
the zero-sync contract the fully-fused ``lax.scan`` round requires.

Two serializers share one schema:

* :func:`round_scalars` — traceable jnp reduction to the per-round scalar
  summary, keyed exactly like the matching ``FLHistory.as_dict`` lists
  (``SCALAR_KEYS``); ``training.distributed`` routes its metrics dict
  through this instead of hand-rolling keys.
* :func:`to_row` — host-side (post-``device_get``) JSON-safe row for the
  JSONL sink, carrying the scalar summary plus the per-client vectors
  (``VECTOR_KEYS``) and the empirical-vs-calibrated erasure-rate pair of
  the bit channel.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# scalar summary keys — MUST match the per-round FLHistory list names
# (training.fl_loop appends one entry per key per round at flush)
SCALAR_KEYS = ('payload_bits', 'retransmissions', 'sign_ok_frac',
               'mod_ok_frac', 'q_mean', 'p_mean', 'sign_agreement',
               'alloc_iters', 'alloc_steps', 'alloc_dual_rounds',
               'alloc_exit_reason',
               'participation_frac',
               'suspect_frac')
# per-client (K,) vectors serialized into JSONL rows when present
VECTOR_KEYS = ('sign_ok', 'mod_ok', 'accepted', 'sign_flips', 'mod_flips',
               'sign_crc_ok', 'mod_crc_ok', 'retx_attempts', 'q', 'p',
               'active', 'suspect', 'suspicion', 'cohort_ids')


class RoundTelemetry(NamedTuple):
    """Per-round uplink + allocation telemetry.  The first five fields
    exist on every transport; the trailing fields are populated by the
    paths that measure them (``channel='bitlevel'`` for the CRC state,
    the packed flat wire for votes, the training loop's
    :meth:`with_allocation` for q/p/objective) and stay ``None``
    elsewhere — ``None`` fields vanish from the pytree, so records of one
    configuration always share a treedef."""
    sign_ok: Array          # (K,) bool — sign packet decoded
    mod_ok: Array           # (K,) bool — modulus packet decoded
    accepted: Array         # (K,) bool — client contributed to the update
    payload_bits: Array     # scalar — total uplink payload this round
    retransmissions: Array  # scalar — total sign resends this round
    sign_flips: Optional[Array] = None    # (K,) channel bit flips (sign)
    mod_flips: Optional[Array] = None     # (K,) channel bit flips (mod)
    sign_crc_ok: Optional[Array] = None   # (K,) first-attempt CRC verify
    mod_crc_ok: Optional[Array] = None    # (K,) modulus CRC verify
    retx_attempts: Optional[Array] = None  # (K,) per-client resend count
    sign_votes: Optional[Array] = None    # (l,) int32 — +1 sign votes among
    #   accepted clients, computed in the packed domain (flat packed wire
    #   with K <= 32 only; the signSGD-style agreement telemetry)
    q: Optional[Array] = None             # (K,) allocated sign success prob
    p: Optional[Array] = None             # (K,) allocated mod success prob
    alloc_objective: Optional[Array] = None  # scalar — eq. (28) objective
    round_idx: Optional[Array] = None     # scalar uint32 — round number
    agreement: Optional[Array] = None     # scalar — precomputed sign-vote
    #   agreement (see :meth:`condensed`); supersedes ``sign_votes`` when set
    alloc_iters: Optional[Array] = None   # scalar int32 — solver outer
    #   iterations to converge this round (early-exit effort telemetry)
    alloc_steps: Optional[Array] = None   # scalar int32 — trips of the
    #   solver's innermost serial loops this round (golden-section steps
    #   of the alternating solve, descent steps of the barrier; 0 on the
    #   uniform branch)
    alloc_dual_rounds: Optional[Array] = None  # scalar int32 — tree
    #   rounds of the alternating solve's dual bisection this round (0
    #   where no SCA round binds, on the barrier and the uniform branch)
    alloc_exit_reason: Optional[Array] = None  # scalar int32 — the
    #   solver's EXIT_* code (core.allocation_jax: 0 converged,
    #   1 iteration cap, 2 non-finite iterate, 3 uniform fallback)
    active: Optional[Array] = None        # (K,) bool — not dropped/stalled
    #   this round (repro.adversary straggler process; None = everyone)
    suspect: Optional[Array] = None       # (K,) bool — screened out by the
    #   packed-domain byzantine defense (weight gated to 0)
    suspicion: Optional[Array] = None     # (K,) f32 — robust-z suspicion
    #   score behind the verdict (adversary.screen, already O(K))
    cohort_ids: Optional[Array] = None    # (K,) uint32 — global device ids
    #   of the sampled cohort (population mode, repro.population; None in
    #   the legacy cohort == population regime)

    # ------------------------------------------------------------------
    def with_allocation(self, q: Array, p: Array,
                        objective: Optional[Array] = None,
                        round_idx: Optional[Array] = None,
                        iters: Optional[Array] = None,
                        exit_reason: Optional[Array] = None,
                        steps: Optional[Array] = None,
                        dual_rounds: Optional[Array] = None
                        ) -> 'RoundTelemetry':
        """Attach the round's allocation state (device arrays, no host
        transfer — pure ``_replace``)."""
        kw: Dict[str, Any] = dict(q=q, p=p)
        if objective is not None:
            kw['alloc_objective'] = objective
        if round_idx is not None:
            kw['round_idx'] = round_idx
        if iters is not None:
            kw['alloc_iters'] = iters
        if exit_reason is not None:
            kw['alloc_exit_reason'] = exit_reason
        if steps is not None:
            kw['alloc_steps'] = steps
        if dual_rounds is not None:
            kw['alloc_dual_rounds'] = dual_rounds
        return self._replace(**kw)

    def condensed(self) -> 'RoundTelemetry':
        """Reduce the (l,) packed-domain vote vector to the agreement
        scalar — its only downstream use — so ring slots stay O(K)
        instead of O(model dim).  Pure jnp reduction, traceable; push
        ``rec.condensed()`` into the ring, not ``rec``.  The adversarial
        per-client fields (active/suspect/suspicion) are already O(K)
        and pass through untouched."""
        if self.sign_votes is None:
            return self
        return self._replace(
            sign_votes=None,
            agreement=sign_agreement(self.sign_votes, self.sign_ok))


def sign_agreement(sign_votes: Optional[Array], sign_ok: Array) -> Array:
    """Packed-domain consensus scalar: mean |2 v_i - K_ok| / K_ok is 1
    when every accepted client agrees on every coordinate's sign, ~0
    under a split vote (signSGD-style telemetry, computed without
    unpacking).  NaN when no sign packet survived or votes are
    unavailable (K > 32 exceeds the vote word).  Traceable."""
    n_ok = jnp.sum(sign_ok.astype(jnp.float32))
    if sign_votes is None:
        return jnp.float32(jnp.nan)
    v = sign_votes.astype(jnp.float32)
    safe = jnp.maximum(n_ok, 1.0)
    agree = jnp.mean(jnp.abs(2.0 * v - n_ok)) / safe
    return jnp.where(n_ok > 0, agree, jnp.nan)


def round_scalars(t: RoundTelemetry) -> Dict[str, Array]:
    """The per-round scalar summary as device scalars — keys are
    ``SCALAR_KEYS``, i.e. exactly the per-round ``FLHistory.as_dict``
    list names.  Traceable: safe inside a jitted train step (the
    shared serializer ``training.distributed`` routes through)."""
    nan = jnp.float32(jnp.nan)
    return {
        'payload_bits': jnp.asarray(t.payload_bits, jnp.float32),
        'retransmissions': jnp.asarray(t.retransmissions, jnp.float32),
        'sign_ok_frac': jnp.mean(t.sign_ok.astype(jnp.float32)),
        'mod_ok_frac': jnp.mean(t.mod_ok.astype(jnp.float32)),
        'q_mean': nan if t.q is None else jnp.mean(
            t.q.astype(jnp.float32)),
        'p_mean': nan if t.p is None else jnp.mean(
            t.p.astype(jnp.float32)),
        'sign_agreement': (jnp.asarray(t.agreement, jnp.float32)
                           if t.agreement is not None
                           else sign_agreement(t.sign_votes, t.sign_ok)),
        'alloc_iters': nan if t.alloc_iters is None else jnp.asarray(
            t.alloc_iters, jnp.float32),
        'alloc_steps': nan if t.alloc_steps is None else jnp.asarray(
            t.alloc_steps, jnp.float32),
        'alloc_dual_rounds': nan if t.alloc_dual_rounds is None
        else jnp.asarray(t.alloc_dual_rounds, jnp.float32),
        'alloc_exit_reason': nan if t.alloc_exit_reason is None
        else jnp.asarray(t.alloc_exit_reason, jnp.float32),
        'participation_frac': nan if t.active is None else jnp.mean(
            t.active.astype(jnp.float32)),
        'suspect_frac': nan if t.suspect is None else jnp.mean(
            t.suspect.astype(jnp.float32)),
    }


# ---------------------------------------------------------------------------
# host-side serialization (post device_get)
# ---------------------------------------------------------------------------

def _np_scalar(x) -> float:
    return float(np.asarray(x))


def to_row(t: RoundTelemetry, round_idx: Optional[int] = None
           ) -> Dict[str, Any]:
    """One JSON-safe JSONL row from a HOST record (after ``device_get`` —
    call at flush time only; this is the host half of the zero-sync
    contract).  Scalars under ``SCALAR_KEYS``, per-client vectors under
    ``VECTOR_KEYS`` (``None`` when the path did not measure them), plus
    the bit channel's empirical-vs-calibrated erasure-rate pair."""
    sign_ok = np.asarray(t.sign_ok)
    mod_ok = np.asarray(t.mod_ok)
    n_ok = float(sign_ok.astype(np.float32).sum())
    if t.agreement is not None:
        agreement = float(np.asarray(t.agreement))
    elif t.sign_votes is not None and n_ok > 0:
        v = np.asarray(t.sign_votes, np.float32)
        agreement = float(np.mean(np.abs(2.0 * v - n_ok)) / n_ok)
    else:
        agreement = math.nan
    if round_idx is None and t.round_idx is not None:
        round_idx = int(np.asarray(t.round_idx))
    row: Dict[str, Any] = {
        'round': round_idx,
        'payload_bits': _np_scalar(t.payload_bits),
        'retransmissions': _np_scalar(t.retransmissions),
        'sign_ok_frac': float(sign_ok.astype(np.float32).mean()),
        'mod_ok_frac': float(mod_ok.astype(np.float32).mean()),
        'q_mean': math.nan if t.q is None else float(
            np.asarray(t.q, np.float32).mean()),
        'p_mean': math.nan if t.p is None else float(
            np.asarray(t.p, np.float32).mean()),
        'sign_agreement': agreement,
        'alloc_iters': math.nan if t.alloc_iters is None
        else _np_scalar(t.alloc_iters),
        'alloc_steps': math.nan if t.alloc_steps is None
        else _np_scalar(t.alloc_steps),
        'alloc_dual_rounds': math.nan if t.alloc_dual_rounds is None
        else _np_scalar(t.alloc_dual_rounds),
        'alloc_exit_reason': math.nan if t.alloc_exit_reason is None
        else _np_scalar(t.alloc_exit_reason),
        'alloc_objective': None if t.alloc_objective is None
        else _np_scalar(t.alloc_objective),
        'participation_frac': math.nan if t.active is None else float(
            np.asarray(t.active, np.float32).mean()),
        'suspect_frac': math.nan if t.suspect is None else float(
            np.asarray(t.suspect, np.float32).mean()),
    }
    for name in VECTOR_KEYS:
        val = getattr(t, name)
        row[name] = None if val is None else np.asarray(val).tolist()
    # bit channel: empirical (CRC-detected) vs calibrated erasure rates.
    # The calibration contract (wire/README.md) is that the DETECTED
    # first-attempt erasure rate reproduces 1 - q / 1 - p.
    if t.sign_crc_ok is not None:
        row['sign_erasure_emp'] = 1.0 - float(
            np.asarray(t.sign_crc_ok, np.float32).mean())
        row['sign_erasure_cal'] = (
            None if t.q is None
            else 1.0 - float(np.asarray(t.q, np.float32).mean()))
    if t.mod_crc_ok is not None:
        row['mod_erasure_emp'] = 1.0 - float(
            np.asarray(t.mod_crc_ok, np.float32).mean())
        row['mod_erasure_cal'] = (
            None if t.p is None
            else 1.0 - float(np.asarray(t.p, np.float32).mean()))
    return row
