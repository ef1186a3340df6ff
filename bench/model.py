"""A configuration file, the architecture module it names, and its weights
from a seed.

A configuration lives in ``bench/configs/<name>.json`` under the key names
of the model's public ``config.json``; its ``"reference"`` key names the
module ``bench/arch/<reference>.py`` that holds everything the harness
needs of that architecture (the program's config, the weight layout, the
float32 reference and its control, FLOPs and bytes; the contract is in
`bench/arch/dense_decoder.py`'s docstring).  An architecture is added by
adding such a module; nothing here or in the harness changes.
`make_params` draws the weights for the module's layout on the device, in
the dtype they are served in, in one jitted call.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ARCH = BENCH / "arch"
# what every architecture module exposes (`shrink` is optional)
CONTRACT = ("model_config", "param_shapes", "logits", "step_flops",
            "step_attn", "kv_token_bytes")


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def arch_path(conf: dict) -> Path:
    """The architecture module a configuration names."""
    return ARCH / f"{conf['reference']}.py"


_LOADED: dict = {}


def load_file(path: Path):
    """The Python file at ``path`` as a module, loaded once a process (an
    architecture's jitted reference then compiles once, as an imported
    module's would).  Architecture modules and metric readers are found
    this way, by file."""
    path = Path(path)
    mod = _LOADED.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"bench_{path.parent.name}_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return mod


def load_arch(conf: dict):
    """The architecture module `arch_path` names."""
    return load_file(arch_path(conf))


def seed_key(seed: int):
    """A JAX key for any whole number: the low 31 bits seed it and the
    bits above are folded in, so seeds past 2**31 stay distinct."""
    import jax
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_params(shapes: dict, dtype: str, seed: int):
    """Every weight of ``shapes`` (an architecture's `param_shapes`: a
    tree of ``(shape, scale)``, scale None for ones), drawn on the device
    from ``seed`` in one jitted call and returned in ``dtype``."""
    import jax
    import jax.numpy as jnp
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    dt = jnp.dtype(dtype)

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
