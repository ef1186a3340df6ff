"""The dense decoder: RMSNorm, half-split RoPE, grouped-query causal
softmax attention with no biases, SwiGLU, and a tied or untied head
(Llama-style, as Yi-6B publishes it).  A configuration file names it with
``"reference": "dense_decoder"``.

This is one of the architecture modules `bench.model.load_arch` finds by
file name; each exposes what the harness needs of its architecture:

- `model_config(conf)`: the program's `ModelConfig` for the file;
- `param_shapes(cfg)`: the ``(shape, scale)`` tree `make_params` draws;
- `logits(conf, params, ids, at, fp8=False)`: the float32 reference and,
  with ``fp8``, its control;
- `step_flops(cfg, lanes)`, `step_attn(cfg, lanes, page, kv_bytes)`: the
  model FLOPs of a step, and the attention kernel's FLOPs and bytes;
- `kv_token_bytes(cfg)`: KV bytes per token over the held layers;
- optionally `shrink(conf)`, which sets the keys only this architecture
  has to the CPU tests' small sizes.

The reference is written from the equations, apart from the program: it
follows `repro/models/reference.py` and imports nothing of the program.
The whole sequence runs through one layer at a time, each layer a jitted
call that upcasts that layer's weights to float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as ref
from bench.flops import step_attn, step_flops  # noqa: F401  (the counts)


def model_config(conf: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        max_context=conf["max_position_embeddings"],
        dtype=conf["torch_dtype"])


def param_shapes(cfg) -> dict:
    """Shape and init scale of every weight, in the layout `DenseLM`
    serves: stacked (L, ...) blocks, an untied head, vocab padded to 256.
    Matrices are N(0, 1/fan_in), embedding and head N(0, 0.02^2), norms 1."""
    L, d, V = cfg.n_layers, cfg.d_model, cfg.padded_vocab
    q, kv, ff = cfg.n_heads * cfg.d_head, cfg.n_kv_heads * cfg.d_head, cfg.d_ff
    shapes = dict(
        emb=((V, d), 0.02), ln_f=((d,), None),
        blocks=dict(ln1=((L, d), None), ln2=((L, d), None),
                    wq=((L, d, q), d ** -0.5), wk=((L, d, kv), d ** -0.5),
                    wv=((L, d, kv), d ** -0.5), wo=((L, q, d), q ** -0.5),
                    w1=((L, d, ff), d ** -0.5), w3=((L, d, ff), d ** -0.5),
                    w2=((L, ff, d), ff ** -0.5)))
    if not cfg.tie_embeddings:
        shapes["lm_head"] = ((V, d), 0.02)
    return shapes


def kv_token_bytes(cfg) -> int:
    """K and V of one token over the held layers, in the served dtype."""
    itemsize = 2 if cfg.dtype in ("bfloat16", "float16") else 4
    return cfg.n_layers * 2 * cfg.n_kv_heads * cfg.d_head * itemsize


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, w, dims, fp8):
    H, Hkv, Dh, eps, theta = dims
    f = lambda name: w[name].astype(jnp.float32)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = ref.rms(x, w["ln1"], eps)
    q = ref.rope(ref.mm(h, f("wq"), fp8).reshape(S, H, Dh), pos, theta)
    k = ref.rope(ref.mm(h, f("wk"), fp8).reshape(S, Hkv, Dh), pos, theta)
    v = ref.mm(h, f("wv"), fp8).reshape(S, Hkv, Dh)
    x = x + ref.mm(ref.attention(q, k, v), f("wo"), fp8)
    h = ref.rms(x, w["ln2"], eps)
    ff = jax.nn.silu(ref.mm(h, f("w1"), fp8)) * ref.mm(h, f("w3"), fp8)
    return x + ref.mm(ff, f("w2"), fp8)


def logits(conf: dict, params, ids, at, fp8: bool = False):
    """Float32 logits ``(len(at), vocab)`` of token sequence ``ids`` at
    positions ``at``; with ``fp8`` the control's."""
    ids = ref.pad_ids(ids)
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
        return ref.head_logits(x, at, params["ln_f"], head,
                               float(conf["rms_norm_eps"]),
                               conf["vocab_size"], fp8)
