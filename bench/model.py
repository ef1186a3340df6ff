"""A configuration file as the program runs it, and its weights from a seed.

A configuration lives in ``bench/configs/<name>.json`` under the key names
of the model's public ``config.json``.  `model_config` turns it into the
program's `ModelConfig`; `make_params` draws the weights for it on the
device, in the dtype they are served in, in one jitted call.  The
reference (`bench/reference.py`) reads the same file and the same weights,
never anything the program made.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


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


def seed_key(seed: int):
    """A JAX key for any whole number: the low 31 bits seed it and the
    bits above are folded in, so seeds past 2**31 stay distinct."""
    import jax
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


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


def make_params(cfg, seed: int):
    """Every weight, drawn on the device from ``seed`` in one jitted call
    and returned in the served dtype."""
    import jax
    import jax.numpy as jnp
    spec = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    dt = jnp.dtype(cfg.dtype)

    def build(key):
        out = []
        for i, (shape, scale) in enumerate(leaves):
            if scale is None:
                out.append(jnp.ones(shape, dt))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * scale).astype(dt))
        return jax.tree.unflatten(tree, out)

    return jax.jit(build)(seed_key(seed))
