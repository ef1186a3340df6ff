"""Plain float32 reference forward pass of the dense decoder family.

Straightforward `jax.numpy` with no kernel, cache, paging or batching: the
whole token sequence through every layer with full causal softmax
attention.  It is written apart from `DenseLM` so that the served path is
compared with an independent statement of the same mathematics (RMSNorm,
half-split RoPE, grouped-query attention, SwiGLU, untied or tied head).
It supports the block `DenseLM` builds, including ``qk_norm``, and not
``sliding_window``.

It runs a layer at a time, each a separate jitted call that upcasts that
layer's weights (bf16 on the chip) to float32, under
``jax.default_matmul_precision("highest")`` so that a TPU does not drop
float32 matrix products to bf16 passes.  At the published widths one
layer's float32 weights and one sequence's attention scores are what it
holds at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (S, H, D) float32; rotate the two halves of the head dim."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs        # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _layer(x, w, cfg):
    f = lambda name: w[name].astype(jnp.float32)
    S = x.shape[0]
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], cfg.norm_eps)
    q = (h @ f("wq")).reshape(S, H, Dh)
    k = (h @ f("wk")).reshape(S, Hkv, Dh)
    v = (h @ f("wv")).reshape(S, Hkv, Dh)
    if cfg.qk_norm:
        q = _rms(q, w["qn"], cfg.norm_eps)
        k = _rms(k, w["kn"], cfg.norm_eps)
    q = _rope(q, pos, cfg.rope_theta)
    k = jnp.repeat(_rope(k, pos, cfg.rope_theta), H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(Dh)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(S, H * Dh) @ f("wo")
    h = _rms(x, w["ln2"], cfg.norm_eps)
    return x + (jax.nn.silu(h @ f("w1")) * (h @ f("w3"))) @ f("w2")


@functools.partial(jax.jit, static_argnames=("cfg",))
def _head(x, ln_f, head, cfg):
    x = _rms(x, ln_f, cfg.norm_eps)
    return (x @ head.astype(jnp.float32).T)[:, :cfg.vocab]


def dense_logits(cfg, params, ids, at, pad_to: int = 512) -> np.ndarray:
    """Float32 logits ``(len(at), cfg.vocab)`` of the token sequence
    ``ids`` at the positions ``at``.

    The sequence is padded at its end to a multiple of ``pad_to`` so that
    sequences of nearby lengths share one compiled layer; causal masking
    keeps the padding out of every real position."""
    ids = np.asarray(ids, np.int32)
    S = -(-len(ids) // pad_to) * pad_to
    ids = np.pad(ids, (0, S - len(ids)))
    with jax.default_matmul_precision("highest"):
        x = params["emb"][jnp.asarray(ids)].astype(jnp.float32)
        for layer in range(cfg.n_layers):
            w = jax.tree.map(lambda a: a[layer], params["blocks"])
            x = _layer(x, w, cfg)
        head = params["emb"] if cfg.tie_embeddings else params["lm_head"]
        out = _head(x[jnp.asarray(np.asarray(at, np.int32))],
                    params["ln_f"], head, cfg)
    return np.asarray(out)
