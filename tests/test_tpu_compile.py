"""Compile every Pallas kernel for a described TPU v5e chip.

Interpret-mode parity (tests/test_wire.py, tests/test_packed_hotpath.py,
tests/test_kernels.py) cannot see what the chip's compiler refuses:
block shapes off the (8, 128) tiling, unsigned reductions, xor
reductions, uint32 -> f32 casts.  These tests lower each pallas_call
through the TPU compiler for a chip that is described, not attached, at
the sizes the system runs:

* the paper CNN payload (n = 62,006 -> G = 2048 padded groups, K = 20
  clients, b = 3), and
* the largest smollm-135m leaf (the 49152 x 576 embedding, K = 4).

Nothing runs, so nothing here checks results or times.  The topology is
described inside a fixture (never at import time): only one process may
hold the TPU library, and pytest-xdist workers each import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import quantize_kernel as qk
from repro.wire import format as wire_fmt
from repro.wire import pack_kernel as wk

BITS = 3
# (payload coordinates n, clients K)
SIZES = {'cnn': (62_006, 20), 'smollm_embed': (49_152 * 576, 4)}
U32, F32 = jnp.uint32, jnp.float32


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)
    cc.reset_cache()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _cases(n: int, k: int):
    """kernel name -> (call, argument (shape, dtype) list) at payload
    size n with k clients, padded the way kernels.ops pads."""
    g = _round_up(wire_fmt.n_groups(n), wk.BLOCK_GROUPS)
    w_corrupt = _round_up(wire_fmt.n_groups(n) * BITS,
                          wk.BLOCK_CORRUPT_WORDS)
    w_fold = _round_up(wire_fmt.n_groups(n) * BITS, wk.BLOCK_FOLD_WORDS)
    tile_rows = _round_up(-(-n // qk.BLOCK_COLS), qk.BLOCK_ROWS)
    tile = ((tile_rows, qk.BLOCK_COLS), F32)
    one = ((1, 1), F32)
    per_client = [((k,), F32)] * 4 + [((k,), U32)]

    def accumulate(per_client_gbar, votes):
        return (lambda sw, qw, gb, mn, st, mo, w, vg: wk.spfl_accumulate_2d(
            sw, qw, gb, mn, st, mo, w, vg, bits=BITS, n_clients=k,
            gbar_per_client=per_client_gbar, with_votes=votes),
            [((k * g, 1), U32), ((k * g, BITS), U32),
             (((k * g) if per_client_gbar else g, wire_fmt.GROUP), F32)]
            + per_client)

    return {
        'pack_2d': (lambda v: wk.pack_2d(v, bits=BITS),
                    [((g, wire_fmt.GROUP), U32)]),
        'unpack_2d': (lambda w: wk.unpack_2d(w, bits=BITS),
                      [((g, BITS), U32)]),
        'quantize_pack_2d': (
            lambda x, r, mn, mx: wk.quantize_pack_2d(x, r, mn, mx,
                                                     bits=BITS),
            [((g, wire_fmt.GROUP), F32)] * 2 + [one, one]),
        'unpack_dequant_2d': (
            lambda sw, qw, gb, mn, st, mo, w: wk.unpack_dequant_2d(
                sw, qw, gb, mn, st, mo, w, bits=BITS),
            [((g, 1), U32), ((g, BITS), U32), ((g, wire_fmt.GROUP), F32)]
            + [one] * 4),
        'fold_words_2d': (wk.fold_words_2d, [((k, w_fold), U32)]),
        'corrupt_fold_2d': (
            lambda s, o, t, a, w: wk.corrupt_fold_2d(
                s, o, t, a, w, n_words=w_corrupt - 7),
            [((1, 2), U32), ((1, 1), U32), ((k, 1), U32), ((k, 1), U32),
             ((k, w_corrupt), U32)]),
        'spfl_accumulate_2d': accumulate(False, True),
        'spfl_accumulate_2d_per_client_gbar': accumulate(True, False),
        'quantize_2d': (
            lambda x, r, mn, mx: qk.quantize_2d(x, r, mn, mx, bits=BITS),
            [tile, tile, one, one]),
    }


@pytest.mark.parametrize('size', sorted(SIZES))
@pytest.mark.parametrize('kernel', sorted(_cases(1, 1)))
def test_kernel_compiles_for_v5e(one_chip, kernel, size):
    call, args = _cases(*SIZES[size])[kernel]
    sds = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
           for shape, dt in args]
    compiled = jax.jit(call).lower(*sds).compile()
    assert 'tpu_custom_call' in compiled.as_text()
