#!/usr/bin/env python3
"""Smoke run of the SP-FL system on a TPU: the quickest proof that the
fused round still imports, compiles and runs on the chip.

    python3 chip_smoke.py              # one chip: device, kernels, cnn, smollm
    python3 chip_smoke.py --chips 4    # four chips: sharded collective only

Phases run in order in this one process; the first failure ends the run
with a non-zero exit and no result line.

* device  — require a TPU backend (no CPU fallback); print versions and
            the compile cache directory.
* kernels — the packed-wire Pallas kernels of the main path
            (``spfl_aggregate_packed``, ``corrupt_fold_words``,
            ``fold_words``) at the paper CNN payload size (n = 62,006,
            K = 20, b = 3) against their jnp twins: integers bit-exact,
            f32 sums within ``ops.aggregate_atol``.
* cnn     — the paper §V operating point (packed wire, bit-level
            channel, per-round eq. (28) solve, fused scan) through
            ``training.fl_loop.build_simulator``: finite losses, a lower
            loss after the rounds, ``tpu_custom_call`` in the compiled
            round.
* smollm  — ``launch.train.run`` on smollm-135m at its published widths
            (d=576, 30 layers, vocab 49152), packed wire, fused scan,
            4 clients, 3 steps: finite losses.
* sharded (``--chips 4`` only) — ``collective='sharded'`` on a 4-device
            client mesh against ``collective='gather'`` at the CNN point:
            same integer telemetry, f32 within the contract, payload rows
            on all four devices.

Times printed here are smoke readings (one process, few repetitions),
not benchmark results.  The last stdout line is the JSON result.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                'src'))
from repro.configs.base import FLConfig  # noqa: E402
from repro.core import transport as TR  # noqa: E402
from repro.data.synthetic import real_cifar_present  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import env as launch_env  # noqa: E402
from repro.launch import shardings as SH  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.training.fl_loop import build_simulator  # noqa: E402
from repro.wire import format as fmt  # noqa: E402

N_CNN, K_CNN, BITS = 62_006, 20, 3      # paper CNN payload, §V cohort


def log(msg: str) -> None:
    print(msg, flush=True)


def _compile(fn, *args):
    """AOT-compile ``fn`` for ``args`` -> (compiled, seconds)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _timed(compiled, *args, reps: int = 3):
    """Warm call, then the mean of ``reps`` calls ending in
    block_until_ready -> (outputs, seconds per call)."""
    out = jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(compiled(*args))
    return out, (time.perf_counter() - t0) / reps


def _require_kernel(compiled, what: str) -> None:
    if 'tpu_custom_call' not in compiled.as_text():
        raise RuntimeError(f'{what}: no tpu_custom_call in the compiled '
                           'program (a jnp twin stood in for the kernel)')


def _require_equal(a, b, what: str) -> None:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or not np.array_equal(a, b):
        bad = (int(np.sum(a != b)) if a.shape == b.shape
               else f'shape {a.shape} vs {b.shape}')
        raise RuntimeError(f'{what}: integers differ ({bad})')


def _require_close(a, b, atol: float, what: str) -> float:
    err = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
    if not err <= atol:
        raise RuntimeError(f'{what}: max |diff| {err:.3e} > {atol:.3e}')
    return err


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get('peak_bytes_in_use')


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(n_chips: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != 'tpu':
        raise RuntimeError(f'no TPU: JAX found {dev.platform!r} devices '
                           '(this smoke run has no CPU fallback)')
    if len(devs) < n_chips:
        raise RuntimeError(f'--chips {n_chips} needs {n_chips} devices, '
                           f'found {len(devs)}')
    log(f'device: {dev.device_kind} x{len(devs)} platform={dev.platform}')
    log(f'versions: jax {jax.__version__} jaxlib {jaxlib.__version__} '
        f'libtpu {importlib.metadata.version("libtpu")}')
    log(f'compile cache: {jax.config.jax_compilation_cache_dir}')
    return dev, len(devs)


def _packed_payloads(k: int, n: int, bits: int, seed: int):
    rng = np.random.RandomState(seed)
    sign = jnp.asarray(rng.choice([-1, 1], (k, n)), jnp.int8)
    qidx = jnp.asarray(rng.randint(0, 2 ** bits, (k, n)), jnp.int32)
    scal = dict(
        gmin=jnp.asarray(rng.uniform(0.0, 0.1, k), jnp.float32),
        gmax=jnp.asarray(rng.uniform(0.5, 1.0, k), jnp.float32),
        mod_ok=jnp.asarray(rng.rand(k) < 0.7, jnp.float32),
        weight=jnp.asarray(rng.uniform(0.0, 2.0, k), jnp.float32),
        sign_ok=jnp.asarray(rng.rand(k) < 0.8),
    )
    gbar = jnp.asarray(rng.uniform(0.0, 1.0, n), jnp.float32)
    return (fmt.pack_bits_ref(fmt.sign_to_bits(sign), 1),
            fmt.pack_bits_ref(qidx, bits), gbar, scal)


def phase_kernels(n: int = N_CNN, k: int = K_CNN, bits: int = BITS):

    sw, qw, gbar, s = _packed_payloads(k, n, bits, seed=0)
    args = (sw, qw, gbar, s['gmin'], s['gmax'], s['mod_ok'], s['weight'],
            s['sign_ok'])

    def aggregate(use_kernel):
        return lambda *a: ops.spfl_aggregate_packed(
            *a, n, bits, use_kernel=use_kernel)

    kern, c_s = _compile(aggregate(True), *args)
    _require_kernel(kern, 'spfl_aggregate_packed')
    (acc_k, votes_k), t_s = _timed(kern, *args)
    twin, _ = _compile(aggregate(False), *args)
    acc_t, votes_t = twin(*args)
    _require_equal(votes_k, votes_t, 'spfl_aggregate_packed votes')
    atol = ops.aggregate_atol(s['weight'], s['gmax'], gbar)
    err = _require_close(acc_k, acc_t, atol, 'spfl_aggregate_packed sum')
    log(f'kernels: spfl_aggregate_packed n={n} K={k} b={bits} votes '
        f'bit-exact, f32 max|diff| {err:.3e} (bound {atol:.3e}); '
        f'compile {c_s:.2f}s, call {t_s * 1e3:.3f}ms')

    rng = np.random.RandomState(1)
    words = jnp.asarray(rng.randint(0, 2 ** 32, (k, qw.shape[1]), np.int64),
                        jnp.uint32)
    ber = jnp.asarray(rng.uniform(0.0, 0.05, k), jnp.float32)
    key = jax.random.PRNGKey(2)

    def corrupt(use_kernel):
        return lambda kk, w, b: ops.corrupt_fold_words(
            kk, w, b, use_kernel=use_kernel)

    kern, c_s = _compile(corrupt(True), key, words, ber)
    _require_kernel(kern, 'corrupt_fold_words')
    got, t_s = _timed(kern, key, words, ber)
    want = jax.jit(corrupt(False))(key, words, ber)
    for g, w, name in zip(got, want, ('received', 'fold', 'flips')):
        _require_equal(g, w, f'corrupt_fold_words {name}')
    flips = int(jnp.sum(got[2]))
    if flips == 0:
        raise RuntimeError('corrupt_fold_words flipped no bit')
    log(f'kernels: corrupt_fold_words K={k} W={words.shape[1]} '
        f'received/fold/flips bit-exact ({flips} flips); '
        f'compile {c_s:.2f}s, call {t_s * 1e3:.3f}ms')

    kern, c_s = _compile(ops.fold_words, got[0])
    _require_kernel(kern, 'fold_words')
    folds, t_s = _timed(kern, got[0])
    _require_equal(folds, jax.jit(fmt.xor_fold)(got[0]), 'fold_words')
    log(f'kernels: fold_words K={k} W={words.shape[1]} bit-exact vs '
        f'xor_fold; compile {c_s:.2f}s, call {t_s * 1e3:.3f}ms')


def phase_cnn(dev, seg_rounds: int = 4, segments: int = 2):

    fl = FLConfig(n_devices=K_CNN, quant_bits=BITS, transport='spfl',
                  wire='packed', channel='bitlevel',
                  allocation_backend='jax', allocation_cadence='per_round',
                  round_fusion='scan', scan_segment_rounds=seg_rounds)
    log('cnn: dataset ' + ('CIFAR-10 (data/cifar-10)' if real_cifar_present()
                           else f'SynthCIFAR (seeded, seed={fl.seed})'))
    sim = build_simulator(fl)
    loss0, _ = sim._global_metrics(sim.params, sim.client_x, sim.client_y,
                                   sim.test_x, sim.test_y)
    loss0 = float(loss0)

    # the simulator's own scan segment, compiled ahead of time so its
    # program can be inspected and timed alone
    body = sim._fused_round_body()
    carry = sim._fused_init_carry(seg_rounds)
    ns = jnp.arange(seg_rounds, dtype=jnp.uint32)
    seg, c_s = _compile(lambda c, r: jax.lax.scan(body, c, r), carry, ns)
    _require_kernel(seg, 'cnn fused round')
    _, t_seg = _timed(seg, carry, ns)
    log(f'cnn: K={K_CNN} n={sim.dim} b={BITS} packed/bitlevel/per_round/'
        f'scan; segment of {seg_rounds} rounds compile {c_s:.2f}s, steady '
        f'{t_seg / seg_rounds * 1e3:.3f}ms/round; peak_bytes_in_use '
        f'{_peak_bytes(dev)}')

    hist = sim.run(seg_rounds * segments)
    losses = np.asarray(hist.loss)
    if not np.all(np.isfinite(losses)) or not np.isfinite(loss0):
        raise RuntimeError(f'cnn: non-finite loss {loss0} -> {losses}')
    if not losses[-1] < loss0:
        raise RuntimeError(f'cnn: loss did not fall ({loss0} -> '
                           f'{losses[-1]})')
    log(f'cnn: global loss {loss0:.4f} -> {losses[-1]:.4f} after '
        f'{seg_rounds * segments} rounds (test acc '
        f'{hist.test_acc[-1]:.3f})')


def phase_smollm(dev, arch: str = 'smollm-135m', batch: int = 8,
                 seq: int = 256, clients: int = 4, steps: int = 3):

    log(f'smollm: arch {arch}, clients={clients} batch={batch}/client '
        f'seq={seq} steps={steps}')
    t0 = time.perf_counter()
    hist = train.run(arch, steps, clients, batch, seq,
                     transport_kind='spfl', allocator='barrier', lr=0.05,
                     bandwidth_hz=10e9, tx_power_dbm=-4.0, wire='packed',
                     allocation_backend='jax', round_fusion='scan')
    wall = time.perf_counter() - t0
    losses = np.asarray(hist['loss'])
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise RuntimeError(f'smollm: bad losses {losses}')
    log(f'smollm: losses {[round(float(x), 4) for x in losses]}; wall '
        f'{wall:.2f}s incl. compile; peak_bytes_in_use {_peak_bytes(dev)}')


def phase_sharded(n: int = N_CNN, k: int = K_CNN, bits: int = BITS):

    mesh = make_host_mesh()
    if mesh.size != 4:
        raise RuntimeError(f'sharded: expected a 4-device mesh, got '
                           f'{mesh.size}')
    g = jax.random.normal(jax.random.PRNGKey(5), (k, n)) * 0.02
    g = jnp.where(g == 0, 1e-4, g)
    grads = jax.device_put(g, SH.client_sharding(mesh))
    shards = grads.addressable_shards
    if (len({sh.device for sh in shards}) != 4
            or any(sh.data.shape != (k // 4, n) for sh in shards)):
        raise RuntimeError('sharded: client rows not spread over 4 devices')
    gbar = jnp.abs(g[0])
    q = jnp.linspace(0.3, 0.9, k)
    p = jnp.linspace(0.4, 0.95, k)
    key = jax.random.PRNGKey(6)

    def aggregate(collective):
        return lambda gr, kk: TR.spfl_aggregate(
            gr, gbar, q, p, bits, 64, kk, wire='packed', channel='bitlevel',
            collective=collective, mesh=mesh)

    sharded, c_s = _compile(aggregate('sharded'), grads, key)
    _require_kernel(sharded, 'sharded collective')
    if 'all-reduce' not in sharded.as_text():
        raise RuntimeError('sharded: no cross-device psum in the program')
    (gh_s, d_s), t_s = _timed(sharded, grads, key)
    # the gathered reference holds every client on one chip: a Mosaic
    # kernel cannot be auto-partitioned over a multi-device program
    one = jax.device_put(g, jax.devices()[0])
    gathered, c_g = _compile(aggregate('gather'), one, key)
    (gh_g, d_g), t_g = _timed(gathered, one, key)
    for name in ('sign_ok', 'mod_ok', 'accepted', 'sign_flips', 'mod_flips',
                 'sign_crc_ok', 'mod_crc_ok', 'sign_votes'):
        a, b = getattr(d_s, name), getattr(d_g, name)
        if (a is None) != (b is None):
            raise RuntimeError(f'sharded: telemetry {name} present on one '
                               'side only')
        if a is not None:
            _require_equal(a, b, f'sharded telemetry {name}')
    _require_equal(d_s.payload_bits, d_g.payload_bits, 'payload_bits')
    w = TR._inverse_prob(d_g.sign_ok, q)
    atol = ops.aggregate_atol(w, jnp.max(jnp.abs(g), axis=1), gbar) / k
    err = _require_close(gh_s, gh_g, atol, 'sharded aggregate')
    flip_devs = {sh.device for sh in d_s.sign_flips.addressable_shards}
    log(f'sharded: K={k} n={n} b={bits} bitlevel on {mesh.size} devices '
        f'({k // mesh.size} clients each); telemetry bit-exact, f32 '
        f'max|diff| {err:.3e} (bound {atol:.3e}); per-client telemetry on '
        f'{len(flip_devs)} devices; sharded compile {c_s:.2f}s call '
        f'{t_s * 1e3:.3f}ms, gathered compile {c_g:.2f}s call '
        f'{t_g * 1e3:.3f}ms')


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--chips', type=int, default=1, choices=(1, 4),
                    help='4: run only the client-sharded collective on a '
                         '4-chip mesh')
    args = ap.parse_args(argv)

    launch_env.configure()

    dev, count = phase_device(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded()
    else:
        phase_kernels()
        log(f'phase kernels done at {time.perf_counter() - t0:.1f}s')
        phase_cnn(dev)
        log(f'phase cnn done at {time.perf_counter() - t0:.1f}s')
        phase_smollm(dev)
    log(f'all phases done at {time.perf_counter() - t0:.1f}s')
    print(json.dumps({'ok': True, 'device': {
        'platform': dev.platform, 'kind': dev.device_kind,
        'count': count}}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
