"""Compile the serving path for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so these tests ask it what a chip
run would: whether Mosaic accepts the paged attention kernel's blocks at
real widths (bf16, D=128, MHA 32/32 and GQA 32/8, pages of 8 and 16, decode
and a 128-token chunk, fp and int8), whether the kernel compiles per shard
under `jax.shard_map` on a 4-chip mesh, and whether the whole fused serving
step at codeqwen1.5-7b widths (4 layers) fits one chip.  Nothing runs, so
they say nothing about results or times.

Only one process at a time may load the TPU library, so the topology is
described inside a module-scoped fixture, never at import: every xdist
worker collects the same tests and only the one given this file loads it.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, D, B, MAXP = 32, 128, 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library reads this as it loads: keep its logs off disk
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def tpu_accum(monkeypatch):
    # models.layers picks its dot accumulation from the backend, which is
    # the CPU here; compile what the chip would run
    monkeypatch.setenv("REPRO_ACCUM_MODE", "preferred")


def _kernel_args(sharding, Sq, Hkv, page, quant, n_pages=1025):
    def s(shape, dtype, spec=None):
        sh = sharding if spec is None else NamedSharding(sharding, spec)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
    heads, pool = (None, None) if not isinstance(sharding, Mesh) else (
        P(None, None, "model", None), P(None, "model", None, None))
    repl = None if heads is None else P()
    pages = (n_pages, Hkv, page, D)
    args = (s((B, Sq, H, D), jnp.bfloat16, heads),
            s(pages, jnp.bfloat16, pool), s(pages, jnp.bfloat16, pool),
            s((B, MAXP), jnp.int32, repl), s((B,), jnp.int32, repl),
            s((B,), jnp.int32, repl))
    q = None
    if quant:
        q = (s(pages, jnp.int8, pool), s(pages, jnp.int8, pool),
             s((n_pages,), jnp.float32, repl),
             s((n_pages,), jnp.float32, repl),
             s((n_pages,), jnp.int32, repl))
    return args, q


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("Sq", [1, 128], ids=["decode", "chunk128"])
@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("Hkv", [32, 8], ids=["mha", "gqa"])
def test_paged_chunk_attention_compiles(one_chip, Hkv, page, Sq, quant):
    args, q = _kernel_args(one_chip, Sq, Hkv, page, quant)
    fn = jax.jit(lambda *a, quant=None: ops.paged_chunk_attention(
        *a, mode="pallas", quant=quant))
    text = fn.lower(*args, quant=q).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_paged_chunk_attention_compiles_under_shard_map(topo, quant):
    """4 chips, kv-heads 32/4 per chip: the kernel compiles per shard, and
    the shards need no collective (each owns whole heads)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    args, q = _kernel_args(mesh, 1, 32, 16, quant)
    fn = jax.jit(lambda *a, quant=None: ops.paged_chunk_attention(
        *a, mode="pallas", quant=quant, mesh=mesh))
    text = fn.lower(*args, quant=q).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" not in text and "all-gather" not in text


def test_fused_step_fits_one_chip_at_codeqwen_widths(one_chip):
    """The whole fused step — scan over 4 layers of codeqwen1.5-7b widths,
    bf16, KV scatter, compiled Pallas attention, FFN and head — at a decode
    bucket of 8 lanes over 1 GiB pools per side (page 16)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.registry import get_model
    cfg = dataclasses.replace(get_config("codeqwen1.5-7b"), n_layers=4)
    model = get_model(cfg)
    put = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.tree.map(lambda a: put(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    L, n_pages, page, Bb, T = cfg.n_layers, 2048, 16, 8, 128
    pool = put((L, n_pages + 1, cfg.n_kv_heads, page, cfg.d_head),
               jnp.bfloat16)
    i32 = lambda *s: put(s, jnp.int32)
    lowered = model.lower_step_paged(
        params, i32(Bb, 1), pool, pool, i32(L, Bb, T), i32(Bb), i32(Bb),
        i32(Bb), i32(L, Bb, 1), i32(L, Bb, 1), None, kernel_mode="pallas")
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    peak = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    assert peak < 16e9, ma
