"""Shared neural-net primitives (pure-functional, pytree params)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def dtype_of(name: str):
    return {'float32': jnp.float32, 'bfloat16': jnp.bfloat16,
            'float16': jnp.float16}[name]


def current_mesh_axes():
    """Axis names and sizes of the ambient mesh (``jax.set_mesh``), or
    (None, None) outside a mesh context."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return None, None
    return tuple(am.axis_names), dict(am.shape)


def maybe_constrain(x: Array, spec_entries) -> Array:
    """with_sharding_constraint if a mesh context exists; no-op otherwise.
    Entries naming axes absent from the mesh, or not dividing the dim,
    are dropped."""
    names, shape = current_mesh_axes()
    if not names:
        return x
    out = []
    for dim, entry in zip(x.shape, spec_entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if not all(a in names for a in axes):
            out.append(None)
            continue
        total = 1
        for a in axes:
            total *= shape[a]
        out.append(entry if dim % total == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*out))


def client_mesh_axes():
    """The non-'model' axes (= FL client / batch axes), or None."""
    names, _ = current_mesh_axes()
    if not names:
        return None
    ca = tuple(n for n in names if n != 'model')
    if not ca:
        return None
    return ca if len(ca) > 1 else ca[0]


# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype) -> Array:
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# normalisation / activations
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def gated_rms_norm(x: Array, z: Array, scale: Array, eps: float = 1e-6) -> Array:
    """Mamba2 output norm: RMSNorm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                    scale, eps)


def softcap(x: Array, cap: float) -> Array:
    """Gemma2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., T, n_heads, head_dim); positions: broadcastable to (..., T)."""
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(head_dim, theta))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]   # add head axis
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def chunked_softmax_xent(x: Array, embed_t: Array, labels: Array,
                         mask: Array, logit_softcap_val: float = 0.0,
                         chunk: int = 512) -> Array:
    """Cross-entropy over a huge vocab without materialising (B,T,V) logits.

    x: (B, T, D) final hidden states; embed_t: (D, V); labels: (B, T) int;
    mask: (B, T) {0,1}.  Computes in sequence chunks so the peak logits
    buffer is (B, chunk, V).
    """
    B, T, D = x.shape
    n_chunks = max(1, (T + chunk - 1) // chunk)
    pad = n_chunks * chunk - T
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    xs = x.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
    ls = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    def body(carry, inp):
        xc, lc, mc = inp
        logits = jnp.einsum('btd,dv->btv', xc, embed_t)
        logits = softcap(logits, logit_softcap_val).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return carry + jnp.sum(nll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls, ms))
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return total / denom
