"""Grouped-query attention (GQA/MQA/MHA) with RoPE, sliding windows,
gemma2 soft-capping, optional QKV bias, and a query-chunked exact
implementation that bounds activation memory to O(q_chunk * S) per head.

The same kernel serves: training (full causal), prefill (causal, cache
write-out) and single-token decode (one query row against a cache).  For
the 500k-context decode shape the KV cache is sharded along the sequence
axis across the mesh; the plain einsum + fp32 softmax formulation below
lets GSPMD lower the softmax reductions and the PV contraction to
flash-decoding-style partial reductions + all-reduce, so no bespoke
collective code is needed (see DESIGN.md §3).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (apply_rope, client_mesh_axes, dense_init,
                                 softcap)

Array = jax.Array
NEG_INF = -2.3819763e38  # max-negative bf16-representable


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        'wq': dense_init(ks[0], d, h * hd, dtype),
        'wk': dense_init(ks[1], d, kv * hd, dtype),
        'wv': dense_init(ks[2], d, kv * hd, dtype),
        'wo': dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p['bq'] = jnp.zeros((h * hd,), dtype)
        p['bk'] = jnp.zeros((kv * hd,), dtype)
        p['bv'] = jnp.zeros((kv * hd,), dtype)
    return p


def _project_qkv(params, cfg: ModelConfig, x: Array, positions: Array):
    """positions: (T,) absolute positions shared across the batch."""
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    q = x @ params['wq']
    k = x @ params['wk']
    v = x @ params['wv']
    if cfg.qkv_bias:
        q = q + params['bq']
        k = k + params['bk']
        v = v + params['bv']
    q = q.reshape(B, T, h, hd)
    k = k.reshape(B, T, kv, hd)
    v = v.reshape(B, T, kv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend(q: Array, k: Array, v: Array, q_pos: Array, kv_pos: Array,
            window: int, cap: float, scale: float,
            constrain=None) -> Array:
    """q: (B,Tq,H,hd) grouped against k/v: (B,S,Kv,hd). Exact softmax."""
    B, Tq, H, hd = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qg = q.reshape(B, Tq, Kv, G, hd)
    # accumulate in f32 on the MXU without materialising an f32 cache copy
    logits = jnp.einsum('btkgh,bskh->bkgts', qg, k,
                        preferred_element_type=jnp.float32)
    if constrain is not None:
        logits = constrain(logits)
    logits = logits * scale
    if cap > 0.0:
        logits = cap * jnp.tanh(logits / cap)
    valid = kv_pos[None, :] <= q_pos[:, None]              # causal
    if window > 0:
        valid &= (q_pos[:, None] - kv_pos[None, :]) < window
    logits = jnp.where(valid[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bkgts,bskh->btkgh', probs.astype(v.dtype), v)
    if constrain is not None:
        out = constrain(out)
    return out.reshape(B, Tq, H, hd)


def multi_head_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                         cap: float = 0.0, q_chunk: int = 1024,
                         constrain=None) -> Array:
    """Query-chunked exact attention; memory O(B*H*q_chunk*S)."""
    B, Tq, H, hd = q.shape
    scale = hd ** -0.5
    if Tq <= q_chunk:
        return _attend(q, k, v, q_pos, kv_pos, window, cap, scale,
                       constrain)
    n = (Tq + q_chunk - 1) // q_chunk
    pad = n * q_chunk - Tq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    qs = q.reshape(B, n, q_chunk, H, hd).swapaxes(0, 1)
    ps = q_pos.reshape(n, q_chunk)

    def body(_, inp):
        qc, pc = inp
        return None, _attend(qc, k, v, pc, kv_pos, window, cap, scale)

    _, outs = jax.lax.scan(body, None, (qs, ps))
    out = outs.swapaxes(0, 1).reshape(B, n * q_chunk, H, hd)
    return out[:, :Tq]


def attention_forward(params, cfg: ModelConfig, x: Array, positions: Array,
                      window: int = 0) -> Array:
    """Full-sequence causal attention (training / prefill trunk)."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = multi_head_attention(
        q, k, v, positions, positions, window=window, cap=cfg.attn_softcap,
        q_chunk=cfg.q_chunk)
    B, T = x.shape[:2]
    return out.reshape(B, T, -1) @ params['wo']


def attention_prefill(params, cfg: ModelConfig, x: Array, positions: Array,
                      window: int = 0):
    """Like forward, but also returns the (k, v) to seed a cache."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = multi_head_attention(
        q, k, v, positions, positions, window=window, cap=cfg.attn_softcap,
        q_chunk=cfg.q_chunk)
    B, T = x.shape[:2]
    return out.reshape(B, T, -1) @ params['wo'], (k, v)


def _constrain_batch_only(x: Array, cfg: ModelConfig) -> Array:
    """decode_cache_layout='batch' (§Perf): pin decode activations to
    batch-only sharding so GSPMD gathers the tiny q instead of the huge KV
    cache (it otherwise propagates the TP head sharding from the weights
    into the attention read and replicates the cache)."""
    if cfg.decode_cache_layout != 'batch':
        return x
    lead = client_mesh_axes()
    if lead is None:
        return x
    spec = jax.sharding.PartitionSpec(lead, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def attention_decode(params, cfg: ModelConfig, x: Array,
                     cache_k: Array, cache_v: Array, pos: Array,
                     window: int = 0):
    """One-token decode.  x: (B, 1, D); cache_k/v: (B, S, Kv, hd).

    ``pos`` is the absolute position of the new token.  The new K/V is
    written at slot ``pos % S`` (ring buffer — for SWA caches S==window so
    this implements the sliding window; for full caches S >= pos+1 always
    holds in our launchers so the modulo is a no-op).
    Returns (y, new_cache_k, new_cache_v).
    """
    B, _, _ = x.shape
    S = cache_k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[None]                                  # (1,)
    q, k, v = _project_qkv(params, cfg, x, positions)
    q = _constrain_batch_only(q, cfg)
    k = _constrain_batch_only(k, cfg)
    v = _constrain_batch_only(v, cfg)
    slot = pos % S
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
    # absolute positions currently held by each cache slot (ring-aware):
    idx = jnp.arange(S, dtype=jnp.int32)
    wrapped = pos - ((slot - idx) % S)          # absolute pos of slot idx
    # never-written slots (wrapped < 0) must FAIL the causal test
    # kv_pos <= q_pos, so they are pushed to +inf, not -inf.
    kv_pos = jnp.where(wrapped >= 0, wrapped, jnp.int32(2 ** 30))
    q_pos = jnp.full((1,), 0, jnp.int32) + pos
    constrain = ((lambda t: _constrain_batch_only(t, cfg))
                 if cfg.decode_cache_layout == 'batch' else None)
    out = multi_head_attention(
        q, cache_k, cache_v, q_pos, kv_pos,
        window=window, cap=cfg.attn_softcap, constrain=constrain)
    y = out.reshape(B, 1, -1) @ params['wo']
    return y, cache_k, cache_v
