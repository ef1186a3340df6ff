"""Float32 pieces that every architecture's plain reference is built from.

Each ``bench/arch/<reference>.py`` writes its layer equations from these
and drives the sequence through them; see `bench/arch/dense_decoder.py`.
Nothing here imports the program or reads anything it made: only a
configuration file and the weights the benchmark drew.

The references run under ``jax.default_matmul_precision("highest")`` so
that a TPU does not drop float32 matmuls to bf16 passes.  Attention runs
in blocks of query rows, so a 16k-token context holds one block's scores
at a time.  ``fp8=True`` is the control: a projection or the head takes
its inputs rounded to float8 (e4m3, one scale per tensor), the precision
step below the bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 1024               # sequences pad to a multiple: few compiled shapes
BLOCK = 512              # query rows per attention block
AT_PAD = 64              # logit rows pad to a multiple, for the same reason


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, b, to_fp8):
    """``a @ b``, with both inputs rounded to float8 for the control."""
    if to_fp8:
        a, b = fp8(a), fp8(b)
    return a @ b


def rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def rope(x, pos, theta):
    """Half-split rotary embedding of ``x`` ``(S, heads, d)``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Grouped-query causal softmax attention of a whole sequence, in
    blocks of `BLOCK` query rows: ``q`` ``(S, H, d)``, ``k`` and ``v``
    ``(S, Hkv, d)``; returns ``(S, H * d)``.  ``S`` is a multiple of
    `BLOCK`."""
    S, H, Dh = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    pos = jnp.arange(S)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(Dh)
        qpos = start + jnp.arange(BLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(block, jnp.arange(0, S, BLOCK)).reshape(S, H * Dh)


def pad_ids(ids) -> np.ndarray:
    """Token ids padded at the end to a multiple of `PAD`.  Padding never
    reaches a real position (causal masking)."""
    ids = np.asarray(ids, np.int32)
    return np.pad(ids, (0, -len(ids) % PAD))


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "to_fp8"))
def _head(x, ln_f, head, eps, vocab, to_fp8):
    x = rms(x, ln_f, eps)
    return mm(x, head.astype(jnp.float32).T, to_fp8)[:, :vocab]


def head_logits(x, at, ln_f, head, eps: float, vocab: int,
                to_fp8: bool) -> np.ndarray:
    """Final norm and output head at rows ``at`` of the hidden states
    ``x``: float32 logits ``(len(at), vocab)``."""
    at = np.asarray(at, np.int32)
    rows = np.pad(at, (0, -len(at) % AT_PAD), mode="edge")
    out = _head(x[jnp.asarray(rows)], ln_f, head, eps, vocab, to_fp8)
    return np.asarray(out)[:len(at)]
