"""Plain float32 reference of the dense decoder, and its fp8 control.

Written from the architecture's equations (RMSNorm, half-split RoPE,
grouped-query causal softmax attention, SwiGLU, untied head), apart from
the program: it imports nothing of it and reads only a configuration file
and the weights the benchmark drew.  It follows `repro/models/
reference.py`, which a later change can delete in favour of this copy.

The whole sequence runs through one layer at a time, each layer a jitted
call that upcasts that layer's weights to float32, under
``jax.default_matmul_precision("highest")`` so that a TPU does not drop
float32 matmuls to bf16 passes.  Attention runs in blocks of query rows,
so a 16k-token context holds one block's scores at a time.

``fp8=True`` is the control: every projection and the head take their
inputs rounded to float8 (e4m3, one scale per tensor), the precision step
below the bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 1024               # sequences pad to a multiple: few compiled shapes
BLOCK = 512              # query rows per attention block
AT_PAD = 64              # logit rows pad to a multiple, for the same reason


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return a @ b


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, w, dims, fp8):
    H, Hkv, Dh, eps, theta = dims
    f = lambda name: w[name].astype(jnp.float32)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], eps)
    q = _rope(_mm(h, f("wq"), fp8).reshape(S, H, Dh), pos, theta)
    k = _rope(_mm(h, f("wk"), fp8).reshape(S, Hkv, Dh), pos, theta)
    v = _mm(h, f("wv"), fp8).reshape(S, Hkv, Dh)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(Dh)
        qpos = start + jnp.arange(BLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, S, BLOCK)).reshape(S, H * Dh)
    x = x + _mm(o, f("wo"), fp8)
    h = _rms(x, w["ln2"], eps)
    ff = jax.nn.silu(_mm(h, f("w1"), fp8)) * _mm(h, f("w3"), fp8)
    return x + _mm(ff, f("w2"), fp8)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "fp8"))
def _head(x, ln_f, head, eps, vocab, fp8):
    x = _rms(x, ln_f, eps)
    return _mm(x, head.astype(jnp.float32).T, fp8)[:, :vocab]


def logits(conf: dict, params, ids, at, fp8: bool = False) -> np.ndarray:
    """Float32 logits ``(len(at), vocab)`` of token sequence ``ids`` at
    positions ``at``.  Padding at the end never reaches a real position
    (causal masking)."""
    ids = np.asarray(ids, np.int32)
    S = -(-len(ids) // PAD) * PAD
    ids = np.pad(ids, (0, S - len(ids)))
    dims = (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], float(conf["rms_norm_eps"]),
            float(conf["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        x = params["emb"][jnp.asarray(ids)].astype(jnp.float32)
        for layer in range(conf["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[layer], params["blocks"])
            x = _layer(x, w, dims, fp8)
        head = params["emb"] if conf["tie_word_embeddings"] \
            else params["lm_head"]
        at = np.asarray(at, np.int32)
        rows = np.pad(at, (0, -len(at) % AT_PAD), mode="edge")
        out = _head(x[jnp.asarray(rows)], params["ln_f"], head,
                    float(conf["rms_norm_eps"]), conf["vocab_size"], fp8)
    return np.asarray(out)[:len(at)]
