"""Jit'd public wrappers around the Pallas kernels.

Handle arbitrary flat/ND inputs: pad to the (BLOCK_ROWS, BLOCK_COLS) tile
grid, run the kernel, unpad.  ``interpret`` defaults to True off-TPU so the
same call sites work on CPU (validation) and TPU (deployment).

The ``*_packed`` family is the materialized-wire hot path: packed uint32
word buffers (repro.wire.format layout) in and out, with the client-side
quantize->pack and PS-side unpack->dequantize->compensate->weight each
fused into one HBM pass (repro.wire.pack_kernel).

Trace-purity contract: every wrapper here is a pure function of its
array arguments — shapes and ``bits``/``k`` are the only static inputs,
all runtime values (gmin/gmax, mod_ok, weights, BER, word offsets, PRNG
keys) pass through ``jnp.asarray`` and stay traced.  The fused
multi-round ``lax.scan`` bodies (training/fl_loop.py round_fusion,
training/distributed.py make_fused_fl_scan) rely on this: the whole
transport — these kernels included — must trace once and iterate
on-device with zero host transfers, so nothing in this module may
branch on a concrete array value or force one to the host.

Screening contract: the byzantine defense (repro.adversary.screen) and
straggler dropout never need kernel changes — both act by zeroing rows
of the existing ``weights`` input.  A zero weight makes the kernel's
row contribution ``0.0 * x`` on already-decoded finite values, which is
a bit-exact no-op in f32 accumulation, so screened/dropped clients cost
nothing and gate-all-ones rounds reproduce the unscreened aggregate
bit for bit.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantize import knob_step
from repro.kernels import quantize_kernel as qk
from repro.wire import corrupt as wire_corrupt
from repro.wire import format as wire_fmt
from repro.wire import pack_kernel as wk

Array = jax.Array


def default_interpret() -> bool:
    return jax.default_backend() != 'tpu'


# ---------------------------------------------------------------------------
# mesh helpers for the sharded (client-axis) collectives
# ---------------------------------------------------------------------------

def default_client_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that enumerate FL clients: every non-'model' axis
    (('pod', 'data') on the multi-pod production mesh, ('data',) on the
    single-pod and host meshes).  This is the single source of the
    client-axis rule — launch.mesh.client_axes delegates here, so the
    sharded collectives' offsets and the launch-side shardings cannot
    drift apart."""
    ca = tuple(a for a in mesh.axis_names if a != 'model')
    return ca or tuple(mesh.axis_names)


def resolve_client_axes(mesh, client_axes=None) -> Tuple[str, ...]:
    """The client axes a sharded collective runs over (default:
    :func:`default_client_axes`), checked to be Auto-typed: the sharded
    paths are GSPMD programs (sharding constraints, shard_map with
    replicated outputs), which Explicit axes — ``jax.make_mesh``'s
    default — reject.  Build meshes with ``launch.mesh.make_mesh``."""
    client_axes = (default_client_axes(mesh) if client_axes is None
                   else tuple(client_axes))
    types = dict(zip(mesh.axis_names, mesh.axis_types))
    bad = [a for a in client_axes
           if types[a] != jax.sharding.AxisType.Auto]
    if bad:
        raise ValueError(f'client mesh axes {bad} must be Auto-typed '
                         '(build the mesh with launch.mesh.make_mesh)')
    return client_axes


def _n_shards(mesh, client_axes) -> int:
    out = 1
    for a in client_axes:
        out *= mesh.shape[a]
    return out


def _axes_arg(client_axes):
    """PartitionSpec / collective axis argument for the client axes."""
    return client_axes if len(client_axes) > 1 else client_axes[0]


def _shard_row0(mesh, client_axes, k_local: int) -> Array:
    """Inside shard_map: the global index of this shard's first client
    row — the linearized client-axis position (row-major over the axis
    tuple, matching how PartitionSpec((a, b)) blocks the leading dim)."""
    idx = jnp.zeros((), jnp.uint32)
    for a in client_axes:
        idx = idx * jnp.uint32(mesh.shape[a]) \
            + jax.lax.axis_index(a).astype(jnp.uint32)
    return idx * jnp.uint32(k_local)


def _pad_clients(x: Array, pad: int) -> Array:
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _to_tiles(flat: Array) -> Tuple[Array, int]:
    """1-D -> tile-aligned 2-D (pad with zeros), returning original size."""
    n = flat.shape[0]
    cols = qk.BLOCK_COLS
    rows = -(-n // cols)
    rows_pad = -(-rows // qk.BLOCK_ROWS) * qk.BLOCK_ROWS
    total = rows_pad * cols
    padded = jnp.pad(flat, (0, total - n))
    return padded.reshape(rows_pad, cols), n


def _s(x) -> Array:
    return jnp.asarray(x, jnp.float32).reshape(1, 1)


def stochastic_quantize_flat(g: Array, rand: Array, gmin, gmax, bits: int,
                             interpret: bool | None = None):
    """Flat (l,) stochastic quantization -> (sign i8 (l,), qidx i32 (l,))."""
    interpret = default_interpret() if interpret is None else interpret
    g2, n = _to_tiles(g.astype(jnp.float32))
    r2, _ = _to_tiles(rand.astype(jnp.float32))
    sign, qidx = qk.quantize_2d(g2, r2, _s(gmin), _s(gmax), bits=bits,
                                interpret=interpret)
    return sign.reshape(-1)[:n], qidx.reshape(-1)[:n]


def dequant_compensate_flat(sign: Array, qidx: Array, gbar: Array,
                            gmin, gmax, mod_ok, weight, bits: int,
                            interpret: bool | None = None) -> Array:
    interpret = default_interpret() if interpret is None else interpret
    s2, n = _to_tiles(sign.astype(jnp.int8))
    q2, _ = _to_tiles(qidx.astype(jnp.int32))
    b2, _ = _to_tiles(gbar.astype(jnp.float32))
    out = qk.dequant_2d(s2, q2, b2, _s(gmin), _s(gmax), _s(mod_ok),
                        _s(weight), bits=bits, interpret=interpret)
    return out.reshape(-1)[:n]


def spfl_roundtrip_flat(g: Array, rand: Array, gbar: Array, gmin, gmax,
                        mod_ok, weight, bits: int,
                        interpret: bool | None = None) -> Array:
    """Fused client+PS pass: one weighted, compensated contribution."""
    interpret = default_interpret() if interpret is None else interpret
    g2, n = _to_tiles(g.astype(jnp.float32))
    r2, _ = _to_tiles(rand.astype(jnp.float32))
    b2, _ = _to_tiles(gbar.astype(jnp.float32))
    out = qk.roundtrip_2d(g2, r2, b2, _s(gmin), _s(gmax), _s(mod_ok),
                          _s(weight), bits=bits, interpret=interpret)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# materialized wire format (packed uint32 payload words)
# ---------------------------------------------------------------------------

def _to_groups(flat: Array, dtype) -> Tuple[Array, int, int]:
    """1-D -> group-major (G_pad, 32) for the pack kernels.  Returns
    (padded 2-D array, original size n, exact group count G)."""
    n = flat.shape[0]
    g = wire_fmt.n_groups(n)
    g_pad = -(-g // wk.BLOCK_GROUPS) * wk.BLOCK_GROUPS
    padded = jnp.pad(flat.astype(dtype), (0, g_pad * wire_fmt.GROUP - n))
    return padded.reshape(g_pad, wire_fmt.GROUP), n, g


def _words_to_grid(words: Array, n: int, bits: int) -> Tuple[Array, int]:
    """Flat payload words -> (G_pad, bits) for the unpack kernels."""
    g = wire_fmt.n_groups(n)
    assert words.shape[0] == g * bits, (words.shape, n, bits)
    g_pad = -(-g // wk.BLOCK_GROUPS) * wk.BLOCK_GROUPS
    w2 = jnp.pad(words.astype(jnp.uint32).reshape(g, bits),
                 ((0, g_pad - g), (0, 0)))
    return w2, g


def _mask_tail(words: Array, n: int) -> Array:
    """Zero the padding lanes of the last 1-bit-plane word so kernel
    output matches the zero-padded reference exactly (the fused quantize
    packs pad coordinates as sign bit 1, since sign(0) transmits as +1)."""
    rem = n % wire_fmt.GROUP
    if rem == 0:
        return words
    mask = jnp.uint32((1 << rem) - 1)
    return words.at[-1].set(words[-1] & mask)


def pack_bits_flat(values: Array, bits: int,
                   interpret: bool | None = None) -> Array:
    """(n,) integer values in [0, 2^bits) -> (ceil(n/32)*bits,) payload
    words (canonical repro.wire.format layout)."""
    interpret = default_interpret() if interpret is None else interpret
    v2, n, g = _to_groups(values, jnp.uint32)
    w = wk.pack_2d(v2, bits=bits, interpret=interpret)
    return w[:g].reshape(-1)


def unpack_bits_flat(words: Array, n: int, bits: int,
                     interpret: bool | None = None) -> Array:
    """Inverse of :func:`pack_bits_flat` -> (n,) uint32 values."""
    interpret = default_interpret() if interpret is None else interpret
    w2, g = _words_to_grid(words, n, bits)
    v = wk.unpack_2d(w2, bits=bits, interpret=interpret)
    return v.reshape(-1)[:n]


def quantize_pack_flat(g: Array, rand: Array, gmin, gmax, bits: int,
                       interpret: bool | None = None
                       ) -> Tuple[Array, Array]:
    """Fused client pass: flat (l,) gradient -> packed (sign_words,
    qidx_words) payloads in ONE read of g (no int8/int32 intermediates)."""
    interpret = default_interpret() if interpret is None else interpret
    g2, n, ng = _to_groups(g, jnp.float32)
    r2, _, _ = _to_groups(rand, jnp.float32)
    sw, qw = wk.quantize_pack_2d(g2, r2, _s(gmin), _s(gmax), bits=bits,
                                 interpret=interpret)
    return _mask_tail(sw[:ng].reshape(-1), n), qw[:ng].reshape(-1)


def unpack_dequant_flat(sign_words: Array, qidx_words: Array, gbar: Array,
                        gmin, gmax, mod_ok, weight, n: int, bits: int,
                        interpret: bool | None = None) -> Array:
    """Fused PS pass: packed payloads -> weighted, compensated
    contribution w * s(g) ⊙ (mod_ok ? Q_v(g) : gbar), one HBM pass."""
    interpret = default_interpret() if interpret is None else interpret
    s2, g_exact = _words_to_grid(sign_words, n, 1)
    q2, _ = _words_to_grid(qidx_words, n, bits)
    b2, _, _ = _to_groups(gbar, jnp.float32)
    step = knob_step(_s(gmin), _s(gmax), bits)
    out = wk.unpack_dequant_2d(s2, q2, b2, _s(gmin), step, _s(mod_ok),
                               _s(weight), bits=bits, interpret=interpret)
    return out.reshape(-1)[:n]


def _spfl_aggregate_packed_jnp(sign_payload: Array, qidx_payload: Array,
                               gbar: Array, gmin: Array, gmax: Array,
                               mod_ok: Array, weight: Array, sign_ok: Array,
                               n: int, bits: int, with_votes: bool
                               ) -> Tuple[Array, Array | None]:
    """Vectorized jnp twin of the decode-once kernel — the live path
    off-TPU, where interpret-mode Pallas is validation-only (same policy
    as the transports using the reference packers on CPU).  Identical
    elementwise op sequence to the analytic aggregation, accumulated in
    the kernel's sequential client order; votes are the same integers."""
    k = sign_payload.shape[0]
    gmin = jnp.asarray(gmin, jnp.float32).reshape(k, 1)
    gmax = jnp.asarray(gmax, jnp.float32).reshape(k, 1)
    sbits = wire_fmt.unpack_bits_ref(sign_payload, n, 1)       # (K, n)
    sign = jnp.where(sbits > 0, 1.0, -1.0)
    qidx = wire_fmt.unpack_bits_ref(qidx_payload, n, bits).astype(
        jnp.float32)
    modulus = gmin + qidx * knob_step(gmin, gmax, bits)
    gb = gbar.astype(jnp.float32)
    gb = gb if gb.ndim == 2 else gb[None, :]
    modulus = jnp.where(jnp.asarray(mod_ok).reshape(k, 1) > 0, modulus, gb)
    contrib = jnp.asarray(weight, jnp.float32).reshape(k, 1) \
        * (sign * modulus)
    acc = contrib[0]
    for i in range(1, k):
        acc = acc + contrib[i]
    votes = None
    if with_votes:
        gate = jnp.asarray(sign_ok).reshape(k, 1).astype(jnp.int32)
        votes = jnp.sum(sbits.astype(jnp.int32) * gate, axis=0)
    return acc, votes


def aggregate_atol(weight, gmax, gbar) -> float:
    """The decode-once f32 contract as an absolute tolerance: a couple of
    ulp per client contribution, accumulated — 4 eps x sum_k w_k
    max(gmax_k, max gbar).  The kernel, its jnp twin and the sharded
    psum agree within it; real decode bugs land at the knob-step scale,
    orders of magnitude above."""
    scale = float(jnp.sum(jnp.asarray(weight)
                          * jnp.maximum(jnp.asarray(gmax), jnp.max(gbar))))
    return 4 * float(jnp.finfo(jnp.float32).eps) * max(scale, 1.0)


def spfl_aggregate_packed(sign_payload: Array, qidx_payload: Array,
                          gbar: Array, gmin: Array, gmax: Array,
                          mod_ok: Array, weight: Array, sign_ok: Array,
                          n: int, bits: int,
                          interpret: bool | None = None,
                          use_kernel: bool | None = None,
                          with_votes: bool | None = None
                          ) -> Tuple[Array, Array | None]:
    """Decode-once PS aggregation, eq. (15)-(17), straight from the
    packed domain: ONE kernel launch over a client grid consumes every
    client's payload words and returns

        (sum_k w_k * s(g_k) ⊙ (mod_ok_k ? Q_v(g_k) : gbar),  sign votes)

    with no (K, n) float intermediate and no per-client unpack passes
    (pack_kernel.spfl_accumulate_kernel).  ``sign_payload`` (K, ceil(n/32))
    and ``qidx_payload`` (K, ceil(n/32)*bits) are payload words in the
    canonical layout; ``gbar`` is the shared (n,) or per-client (K, n)
    compensation modulus; the per-client scalars are (K,) arrays.

    Sign votes are the per-coordinate count of clients with an accepted
    sign packet voting +1, computed in the packed domain (transposed
    vote words + one ``lax.population_count`` per bit-plane); ``None``
    when K exceeds the 32-client vote word capacity.  The caller divides
    the sum by K for the mean — the kernel's client accumulation order
    matches ``transport._seq_client_mean``, so the only difference from
    the jnp paths is the backend FMA-contracting the kernel's fused
    mul+add chains (a couple of ulp; decoded integers and votes are
    bit-exact).

    Dispatch: the Pallas kernel on TPU — or when ``use_kernel`` forces
    it (interpret-mode parity tests) — otherwise the vectorized jnp twin
    (interpret-mode Pallas on CPU is validation, not a fast path; same
    policy as the transports' reference packers).  ``with_votes=False``
    skips all vote work (the tree transports discard votes; the sharded
    collective uses it to keep the cross-shard psum to the f32 partials
    alone); the default ``None`` computes votes whenever K fits the
    32-client vote word."""
    interpret = default_interpret() if interpret is None else interpret
    if use_kernel is None:
        use_kernel = not interpret
    k = sign_payload.shape[0]
    if with_votes is None:
        with_votes = True
    with_votes = with_votes and k <= wk.MAX_VOTE_CLIENTS
    if not use_kernel:
        return _spfl_aggregate_packed_jnp(
            sign_payload, qidx_payload, gbar, gmin, gmax, mod_ok, weight,
            sign_ok, n, bits, with_votes)
    g = wire_fmt.n_groups(n)
    g_pad = -(-g // wk.BLOCK_GROUPS) * wk.BLOCK_GROUPS

    def to_grid(words: Array, width: int) -> Array:
        w = words.astype(jnp.uint32).reshape(k, g, width)
        return jnp.pad(w, ((0, 0), (0, g_pad - g), (0, 0))).reshape(
            k * g_pad, width)

    per_client = gbar.ndim == 2
    gb = gbar.astype(jnp.float32).reshape(k if per_client else 1, -1)
    gb = jnp.pad(gb, ((0, 0), (0, g_pad * wire_fmt.GROUP - n)))
    gb = gb.reshape(-1, wire_fmt.GROUP)

    def col(x, dt) -> Array:
        return jnp.asarray(x).astype(dt).reshape(k)

    # knob step precomputed with the analytic dequantizer's own
    # quantize.knob_step — an in-kernel constant division would
    # strength-reduce to a reciprocal multiply and drift a ulp
    step = knob_step(col(gmin, jnp.float32), col(gmax, jnp.float32), bits)
    acc, votes = wk.spfl_accumulate_2d(
        to_grid(sign_payload, 1), to_grid(qidx_payload, bits), gb,
        col(gmin, jnp.float32), step,
        col(mod_ok, jnp.float32), col(weight, jnp.float32),
        col(sign_ok, jnp.uint32), bits=bits, n_clients=k,
        gbar_per_client=per_client, with_votes=with_votes,
        interpret=interpret)
    votes_out = (votes.reshape(-1)[:n].astype(jnp.int32)
                 if with_votes else None)
    return acc.reshape(-1)[:n], votes_out


def spfl_aggregate_packed_sharded(sign_payload: Array, qidx_payload: Array,
                                  gbar: Array, gmin: Array, gmax: Array,
                                  mod_ok: Array, weight: Array,
                                  sign_ok: Array, n: int, bits: int, *,
                                  mesh,
                                  client_axes: Optional[tuple] = None,
                                  with_votes: bool = True,
                                  interpret: bool | None = None,
                                  use_kernel: bool | None = None
                                  ) -> Tuple[Array, Array | None]:
    """Shard-local decode-once aggregation + one psum: the mesh-scale
    form of :func:`spfl_aggregate_packed`.

    The gathered form consumes the full (K, W) payload buffers in one
    launch — the right shape on a single chip, but when the client axis
    is sharded over ``client_axes`` GSPMD must all-gather every client's
    packed payload first, forfeiting the packed-domain byte win exactly
    where it matters (the uneven-resource uplink of PAPER.md §II).  This
    wrapper instead ``shard_map``s the decode-once pass: every device
    runs the accumulation kernel (or its jnp twin — same dispatch policy
    as the gathered form) over only its *local* clients' (K_local, W)
    words, then a single ``lax.psum`` over the client axes finishes the
    client sum — the only cross-device traffic per call is the
    n-coordinate f32 partial (plus an n-int32 vote partial when
    ``with_votes``), vs the K*W-word all-gather of the gathered lowering.

    Semantics vs the gathered path:

    * integers (decoded signs/knobs, sign votes) are bit-exact — vote
      partials are int32 popcounts and integer addition commutes across
      the psum;
    * the f32 accumulator agrees to the documented few-ulp contract:
      clients still accumulate sequentially *within* a shard, and the
      psum reassociates the per-shard partials — bounded reordering
      wobble on top of the FMA contraction the gathered kernel already
      has (see transport.__doc__);
    * votes ride per-shard vote words, so capacity is 32 clients *per
      shard* (vs 32 total gathered): with K <= 32*n_shards the sharded
      path still surfaces votes.  Pass ``with_votes=False`` (the tree
      transports do) to skip the vote psum entirely.

    A ragged K (not divisible by the shard count) is padded with
    zero-weight, vote-gated-off dummy clients whose contributions are
    exact zeros in both domains.
    """
    client_axes = resolve_client_axes(mesh, client_axes)
    shards = _n_shards(mesh, client_axes)
    axes = _axes_arg(client_axes)
    k = sign_payload.shape[0]
    k_pad = -(-k // shards) * shards
    per_client_gbar = gbar.ndim == 2
    gbar = jnp.asarray(gbar, jnp.float32)
    gmin = jnp.asarray(gmin, jnp.float32).reshape(k)
    gmax = jnp.asarray(gmax, jnp.float32).reshape(k)
    mod_ok = jnp.asarray(mod_ok, jnp.float32).reshape(k)
    weight = jnp.asarray(weight, jnp.float32).reshape(k)
    sign_ok = jnp.asarray(sign_ok).reshape(k)
    if k_pad != k:
        pad = k_pad - k
        sign_payload = _pad_clients(sign_payload.astype(jnp.uint32), pad)
        qidx_payload = _pad_clients(qidx_payload.astype(jnp.uint32), pad)
        if per_client_gbar:
            gbar = _pad_clients(gbar, pad)
        gmin, gmax, mod_ok = (_pad_clients(x, pad)
                              for x in (gmin, gmax, mod_ok))
        weight = _pad_clients(weight, pad)          # w = 0: exact-zero rows
        sign_ok = _pad_clients(sign_ok.astype(bool), pad)   # vote gate off
    votes_on = with_votes and (k_pad // shards) <= wk.MAX_VOTE_CLIENTS
    pc, pc2 = P(axes), P(axes, None)
    in_specs = (pc2, pc2, pc2 if per_client_gbar else P(None),
                pc, pc, pc, pc, pc)
    out_specs = (P(None), P(None)) if votes_on else (P(None),)

    def local(sp, qp, gb, mn, mx, mo, w, so):
        with jax.named_scope('obs/decode_aggregate'):
            acc, votes = spfl_aggregate_packed(
                sp, qp, gb, mn, mx, mo, w, so, n, bits,
                interpret=interpret, use_kernel=use_kernel,
                with_votes=votes_on)
        with jax.named_scope('obs/psum'):
            acc = jax.lax.psum(acc, axes)
            if votes_on:
                return acc, jax.lax.psum(votes, axes)
        return (acc,)

    out = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)(
        sign_payload, qidx_payload, gbar, gmin, gmax, mod_ok, weight,
        sign_ok)
    return out[0], (out[1] if votes_on else None)


def corrupt_fold_words(key, words: Array, ber,
                       interpret: bool | None = None,
                       use_kernel: bool | None = None,
                       word0=0, mesh=None,
                       client_axes: Optional[tuple] = None
                       ) -> Tuple[Array, Array, Array]:
    """Fused bit-channel pass over (K, W) word buffers:
    -> (received, per-client flip-mask xor-fold, per-client flip count).

    Dispatch: the fused Pallas kernel (pack_kernel.corrupt_fold_2d) by
    default — on CPU it runs in interpret mode, where the pallas_call
    boundary also stops the XLA CPU fusion pass from re-running the
    32-round hash chain once per downstream consumer (measured 2.3x on
    the composed bitlevel round).  ``use_kernel=False`` selects the
    bit-identical jnp twin (wire.corrupt.corrupt_fold); both run the
    same counter PRF over the same global bit indices, so the choice
    never changes a single bit, and neither materializes a (..., W, 32)
    random tensor.

    ``word0`` offsets the counter stream (a shard holding rows
    [r0, r0+K_local) passes r0*W).  ``mesh`` switches to the shard-local
    form: the pass runs under shard_map over ``client_axes`` with each
    shard deriving its own offset, so a client-sharded buffer is
    corrupted without ever being gathered — and, because the counter
    PRF addresses *global* bit indices, the received bits are identical
    to the gathered draw."""
    interpret = default_interpret() if interpret is None else interpret
    if use_kernel is None:
        use_kernel = True
    if mesh is not None:
        if not (isinstance(word0, int) and word0 == 0):
            raise ValueError('word0 and mesh are mutually exclusive: the '
                             'sharded form derives each shard\'s offset '
                             'from its mesh position')
        return _corrupt_fold_words_sharded(key, words, ber, mesh,
                                           client_axes, interpret,
                                           use_kernel)
    if not use_kernel:
        return wire_corrupt.corrupt_fold(key, words, ber, word0)
    k, w_n = words.shape
    w_pad = -(-w_n // wk.BLOCK_CORRUPT_WORDS) * wk.BLOCK_CORRUPT_WORDS
    padded = jnp.pad(words.astype(jnp.uint32), ((0, 0), (0, w_pad - w_n)))
    seeds = wire_corrupt.seeds_from_key(key).reshape(1, 2)
    off = jnp.asarray(word0).astype(jnp.uint32).reshape(1, 1)
    thresh, allf = wire_corrupt.flip_threshold(
        jnp.broadcast_to(jnp.asarray(ber, jnp.float32), (k,)))
    rx, fold, flips = wk.corrupt_fold_2d(
        seeds, off, thresh.reshape(k, 1),
        allf.astype(jnp.uint32).reshape(k, 1),
        padded, n_words=w_n, interpret=interpret)
    return (rx[:, :w_n], wire_fmt.xor_fold(fold),
            jnp.sum(flips, axis=1, dtype=jnp.int32))


def _corrupt_fold_words_sharded(key, words: Array, ber, mesh, client_axes,
                                interpret, use_kernel):
    """Shard-local corrupt+fold: pads K to the shard grid, runs the
    fused pass per shard at that shard's global word offset, returns the
    client-sharded results (bit-identical to the gathered draw)."""
    client_axes = resolve_client_axes(mesh, client_axes)
    shards = _n_shards(mesh, client_axes)
    axes = _axes_arg(client_axes)
    k, w_n = words.shape
    k_pad = -(-k // shards) * shards
    k_local = k_pad // shards
    padded = _pad_clients(words.astype(jnp.uint32), k_pad - k)
    ber_k = jnp.broadcast_to(jnp.asarray(ber, jnp.float32), (k,))
    ber_p = jnp.pad(ber_k, (0, k_pad - k))
    key_arr = jnp.asarray(key)

    def local(kk, wl, bl):
        row0 = _shard_row0(mesh, client_axes, k_local)
        return corrupt_fold_words(kk, wl, bl, interpret=interpret,
                                  use_kernel=use_kernel,
                                  word0=row0 * jnp.uint32(w_n))

    rx, fold, flips = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(*([None] * key_arr.ndim)), P(axes, None), P(axes)),
        out_specs=(P(axes, None), P(axes), P(axes)),
        check_vma=False)(key_arr, padded, ber_p)
    return rx[:k], fold[:k], flips[:k]


def fold_words(words: Array, interpret: bool | None = None,
               mesh=None, client_axes: Optional[tuple] = None) -> Array:
    """Per-client xor-fold of (K, W) word buffers -> (K,) uint32: the
    Pallas form of repro.wire.format.xor_fold — the live PS-side CRC
    reduction of the bit-level transports (repro.core.bitchannel folds
    received buffers through it).  Pads W to the fold-block grid with
    zeros (the xor identity).  With ``mesh`` the fold runs shard-locally
    over ``client_axes`` (the verdicts are per-client, so no cross-shard
    reduction exists — shard_map just keeps the opaque kernel call from
    making GSPMD gather the payload rows)."""
    interpret = default_interpret() if interpret is None else interpret
    if mesh is not None:
        client_axes = resolve_client_axes(mesh, client_axes)
        shards = _n_shards(mesh, client_axes)
        axes = _axes_arg(client_axes)
        k = words.shape[0]
        k_pad = -(-k // shards) * shards
        padded = _pad_clients(words, k_pad - k)
        out = jax.shard_map(
            lambda wl: fold_words(wl, interpret=interpret),
            mesh=mesh, in_specs=(P(axes, None),), out_specs=P(axes),
            check_vma=False)(padded)
        return out[:k]
    k, w_n = words.shape
    w_pad = -(-w_n // wk.BLOCK_FOLD_WORDS) * wk.BLOCK_FOLD_WORDS
    padded = jnp.pad(words.astype(jnp.uint32), ((0, 0), (0, w_pad - w_n)))
    return wire_fmt.xor_fold(wk.fold_words_2d(padded, interpret=interpret))
