"""Compile the serving path for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so these tests ask it what a chip
run would: whether Mosaic accepts the paged attention kernel's blocks at
real widths (bf16, D=128, MHA 32/32 and GQA 32/8, pages of 8 and 16, decode
and a 128-token chunk, fp and int8), whether the kernel compiles per shard
under `jax.shard_map` on a 4-chip mesh, whether the whole fused serving
step at codeqwen1.5-7b widths (4 layers) fits one chip, and whether the
fused step keeps its stacked KV pools in place (no pool-sized temporary,
no copy of a pool) at yi-6b-8l's benchmark shapes on one chip and per
shard on a 4-chip mesh.  Nothing runs, so they say nothing about results
or times.

Only one process at a time may load the TPU library, so the topology is
described inside a module-scoped fixture, never at import: every xdist
worker collects the same tests and only the one given this file loads it.
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, D, B, MAXP, LAYERS = 32, 128, 8, 64, 2


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
        P(None, None, "model", None), P(None, None, "model", None, None))
    repl = None if heads is None else P()
    pages = (LAYERS, n_pages, Hkv, page, D)
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
    return args, q, s((), jnp.int32, repl)


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("Sq", [1, 128], ids=["decode", "chunk128"])
@pytest.mark.parametrize("page", [8, 16])
@pytest.mark.parametrize("Hkv", [32, 8], ids=["mha", "gqa"])
def test_paged_chunk_attention_compiles(one_chip, Hkv, page, Sq, quant):
    args, q, layer = _kernel_args(one_chip, Sq, Hkv, page, quant)
    fn = jax.jit(lambda *a, quant=None, layer=None: ops.paged_chunk_attention(
        *a, mode="pallas", quant=quant, layer=layer))
    text = fn.lower(*args, quant=q, layer=layer).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_paged_chunk_attention_compiles_under_shard_map(topo, quant):
    """4 chips, kv-heads 32/4 per chip: the kernel compiles per shard, and
    the shards need no collective (each owns whole heads)."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    args, q, layer = _kernel_args(mesh, 1, 32, 16, quant)
    fn = jax.jit(lambda *a, quant=None, layer=None: ops.paged_chunk_attention(
        *a, mode="pallas", quant=quant, mesh=mesh, layer=layer))
    text = fn.lower(*args, quant=q, layer=layer).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" not in text and "all-gather" not in text


def _compile_step(sharding, cfg, n_pages, Bb, Sq, T, page=16):
    """Compile the fused step for `cfg` at one (lanes, tokens, width)
    bucket over bf16 pools of ``n_pages`` slots (+ the trash page), on one
    chip or, for a `Mesh`, with the pools sharded by kv-heads over it.
    Returns (compiled, pool shape, bytes of both pools)."""
    from repro.models.registry import get_model
    model = get_model(cfg)
    pool_sharding = None
    if isinstance(sharding, Mesh):
        pool_sharding = NamedSharding(sharding,
                                      P(None, None, "model", None, None))
        sharding = NamedSharding(sharding, P())
    put = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    params = jax.tree.map(lambda a: put(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    L = cfg.n_layers
    shape = (L, n_pages + 1, cfg.n_kv_heads, page, cfg.d_head)
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                sharding=pool_sharding or sharding)
    i32 = lambda *s: put(s, jnp.int32)
    lowered = model.lower_step_paged(
        params, i32(Bb, Sq), pool, pool, i32(L, Bb, T), i32(Bb), i32(Bb),
        i32(Bb), i32(L, Bb, Sq), i32(L, Bb, Sq), None, kernel_mode="pallas",
        pool_sharding=pool_sharding)
    return lowered.compile(), shape, 2 * 2 * int(np.prod(shape))


def test_fused_step_fits_one_chip_at_codeqwen_widths(one_chip):
    """The whole fused step — scan over 4 layers of codeqwen1.5-7b widths,
    bf16, KV write, compiled Pallas attention, FFN and head — at a decode
    bucket of 8 lanes over 1 GiB pools per side (page 16), with no
    pool-sized temporary beside the pools."""
    import dataclasses
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("codeqwen1.5-7b"), n_layers=4)
    compiled, _, pool_bytes = _compile_step(one_chip, cfg, 2048, 8, 1, 128)
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    peak = ma.argument_size_in_bytes + ma.output_size_in_bytes \
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    assert peak < 16e9, ma
    assert ma.temp_size_in_bytes < 0.05 * pool_bytes, ma


@pytest.mark.parametrize("Bb,Sq,T", [(32, 1, 256), (16, 256, 256)],
                         ids=["decode", "mixed"])
def test_fused_step_keeps_pools_in_place(one_chip, Bb, Sq, T):
    """At yi-6b-8l's benchmark shapes (8 layers, 15,000 slots + trash, 4 KV
    heads, page 16, bf16) the step carries both stacked pools through the
    layer scan and writes KV in their own layout: its temporaries stay far
    below the pools' bytes, and no copy produces an array shaped like a
    pool or one layer of it (a per-layer slice, a stacked output or a
    relayout would each be one)."""
    import dataclasses
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("yi-6b"), n_layers=8,
                              max_context=4096)
    compiled, shape, pool_bytes = _compile_step(one_chip, cfg, 15000,
                                                Bb, Sq, T)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 0.05 * pool_bytes, ma
    shapes = ["bf16[" + ",".join(map(str, s)) + "]"
              for s in (shape, shape[1:])]
    copies = [ln for ln in compiled.as_text().splitlines()
              if any(f"= {s}" in ln for s in shapes)
              and (" copy(" in ln or re.match(r"\s*(ROOT )?%copy", ln))]
    assert not copies, copies[:3]


@pytest.mark.parametrize("Bb,Sq,T", [(8, 1, 64), (4, 128, 64)],
                         ids=["decode", "mixed"])
def test_fused_step_keeps_pools_in_place_under_shard_map(topo, Bb, Sq, T):
    """4 chips, yi-6b widths (one KV head a chip), 4 layers, 2,048 slots:
    the KV write and the attention kernel run per kv-head shard on the
    carried pools, nothing gathers a pool, and a shard's temporaries stay
    far below its pools' bytes."""
    import dataclasses
    from repro.configs import get_config
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",))
    cfg = dataclasses.replace(get_config("yi-6b"), n_layers=4)
    compiled, _, pool_bytes = _compile_step(mesh, cfg, 2048, Bb, Sq, T)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 0.05 * pool_bytes / 4, ma
