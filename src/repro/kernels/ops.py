"""Public jit'd kernel entrypoints with automatic backend dispatch:
Pallas on TPU (interpret=False), interpret-mode on CPU for validation,
pure-jnp oracle as the universal fallback.  On a device mesh the serving
kernel runs per shard inside `jax.shard_map` over the ``model`` axis: a
Mosaic kernel cannot be partitioned by the compiler.

Tile sizes default to "largest divisor of the (bucketed) sequence length
<= 128" so the serving path never has to thread static block shapes through
its jit boundary — with power-of-two shape buckets this resolves to
min(S, 128), the hardware-aligned tile.  ``q_offset`` is traced end to end
(scalar-prefetch SMEM inside the Pallas kernel), which is what makes one
compiled prefill kernel serve every turn/context length in a bucket."""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.flash_prefill import flash_prefill as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.paged_attention import paged_chunk_attention as _chunk
from repro.kernels.paged_attention import \
    paged_chunk_attention_quant as _chunk_quant


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def serving_kernel_mode(requested: str = "auto", *, meshed: bool = False,
                        heads_sharded: bool = True) -> str:
    """Resolve the serving backend's attention-kernel mode to a concrete
    ``pallas`` | ``interpret`` | ``ref``.

    ``auto`` is compiled Pallas on TPU, meshed or not: on a mesh the
    kernel runs per shard inside `jax.shard_map` over kv-heads (the
    compiler refuses to partition a Mosaic kernel).  On CPU ``auto`` is
    interpret-mode Pallas, except on a mesh, where it is ``ref``: the jnp
    oracle is plain HLO that GSPMD partitions along the sharded kv-head
    (or split-K page-slot) axis, whereas interpret-mode Pallas walks the
    page grid in software per device and would serialize the mesh.

    A Pallas mode on a mesh needs pools sharded by kv-heads
    (``heads_sharded``).  The split-K page-slot fallback (GQA with
    n_kv_heads % tp != 0) has no per-shard kernel, so it raises instead
    of quietly serving from the oracle."""
    mode = requested
    if mode == "auto":
        mode = "pallas" if _on_tpu() else ("ref" if meshed else "interpret")
    if meshed and mode != "ref" and not heads_sharded:
        raise ValueError(
            f"kernel mode {mode!r} on a mesh needs the KV pools sharded by "
            f"kv-heads; this pool is split on page slots or replicated "
            f"(n_kv_heads not divisible by tp) — choose tp dividing "
            f"n_kv_heads")
    return mode


def _auto_tile(n: int, cap: int = 128) -> int:
    """Largest divisor of n that is <= cap (n itself when n <= cap).  A
    long sequence with only tiny divisors would silently degrade to an
    almost-elementwise grid — reject it loudly instead; pad to a bucketed
    length (the serving path always does) or pass explicit tiles."""
    t = min(n, cap)
    while t > 1 and n % t:
        t -= 1
    if n > cap and t < 8:
        raise ValueError(
            f"no usable tile for length {n} (best divisor <= {cap} is {t}); "
            f"pad to a power-of-two bucket or pass bq/bk explicitly")
    return max(t, 1)


def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                    mode: str = "auto"):
    """mode: auto | pallas | interpret | ref"""
    if mode == "ref":
        return ref.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                       ctx_lens)
    interpret = not _on_tpu() if mode == "auto" else (mode == "interpret")
    return _paged(q, k_pages, v_pages, block_tables, ctx_lens,
                  interpret=interpret)


def paged_chunk_attention(q, k_pages, v_pages, block_tables, q_offsets,
                          ctx_lens, mode: str = "auto", bq=None,
                          quant=None, mesh=None):
    """Unified mixed-batch serving attention (decode = 1-token chunk).
    mode: auto | pallas | interpret | ref.  ``quant``: optional
    (kq_pages, vq_pages, k_scales, v_scales, page_quant) mixed-precision
    shadow state — pages flagged quantized dequantize inside the kernel
    (or oracle) instead of reading the fp pool.

    ``mesh``: a mesh with a ``model`` axis over which q (heads) and the
    pools (kv-heads, dim 1) are sharded.  A Pallas mode then runs the
    kernel per shard under `jax.shard_map` (tables, offsets, context
    lengths, scales and precision bits replicated); ``ref`` ignores it
    and lets GSPMD partition the oracle."""
    if mode == "ref":
        return ref.paged_chunk_attention_ref(q, k_pages, v_pages,
                                             block_tables, q_offsets,
                                             ctx_lens, quant=quant)
    interpret = not _on_tpu() if mode == "auto" else (mode == "interpret")
    bq = _auto_tile(q.shape[1]) if bq is None else bq

    def kernel(q, k_pages, v_pages, block_tables, q_offsets, ctx_lens,
               quant):
        if quant is not None:
            kq, vq, ks, vs, pq = quant
            return _chunk_quant(q, k_pages, v_pages, kq, vq, ks, vs, pq,
                                block_tables, q_offsets, ctx_lens,
                                bq=bq, interpret=interpret)
        return _chunk(q, k_pages, v_pages, block_tables, q_offsets,
                      ctx_lens, bq=bq, interpret=interpret)

    if mesh is not None:
        heads = P(None, None, "model", None)
        pool = P(None, "model", None, None)
        quant_spec = None if quant is None else (pool, pool, P(), P(), P())
        kernel = jax.shard_map(
            kernel, mesh=mesh, check_vma=False, out_specs=heads,
            in_specs=(heads, pool, pool, P(), P(), P(), quant_spec))
    return kernel(q, k_pages, v_pages, block_tables, q_offsets, ctx_lens,
                  quant)


def flash_prefill(q, k, v, q_offset=0, mode: str = "auto",
                  bq=None, bk=None):
    if mode == "ref":
        return ref.flash_prefill_ref(q, k, v, q_offset)
    interpret = not _on_tpu() if mode == "auto" else (mode == "interpret")
    bq = _auto_tile(q.shape[1]) if bq is None else bq
    bk = _auto_tile(k.shape[1]) if bk is None else bk
    return _flash(q, k, v, q_offset, bq=bq, bk=bk, interpret=interpret)
