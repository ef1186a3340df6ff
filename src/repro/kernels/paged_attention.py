"""Paged attention — Pallas TPU kernels with block-table indirection.

Two kernels share the flash-decoding structure (page dimension innermost,
running (m, l, acc) accumulators in VMEM scratch, scalar-prefetch block
tables):

* ``paged_attention`` — the single-token decode kernel (one query row per
  lane), kept as the minimal reference shape;
* ``paged_chunk_attention`` — the unified mixed-batch kernel the serving
  step dispatches: every lane carries a CHUNK of queries at a per-lane
  ``q_offset`` (decode lanes are the one-token chunk), so one dispatch
  covers chunked prefill and batched decode together.

The page pool lives in HBM; the grid walks (batch, kv_head, page) with the
page dimension innermost (sequential on a TPU core).  Block tables and
context lengths ride in scalar-prefetch SMEM so each page's DMA source
address is computed *before* the step — the TPU analogue of vLLM's
PagedAttention gather, reshaped for VMEM/MXU:

  * one (page_size x D) K tile + V tile per grid step, resident in VMEM;
  * flash-decoding style running (m, l, acc) accumulators in VMEM scratch
    carried across the page dimension;
  * GQA: the q block holds all G = H/Hkv query heads of one kv head, so the
    MXU contraction is (G x D) @ (D x page_size).

Pages are the unit SYMPHONY migrates between tiers/nodes, so serving decode
reads KV exactly in the layout the node manager stores it.  That layout is
HEAD-MAJOR, ``(P, Hkv, page, D)`` per layer: one grid step's K/V block is
``(None, None, page, D)``, whose last two dims are a whole (page, D) tile —
Mosaic requires a block's last two dims to be (8, 128)-divisible or equal
to the array's, which a token-major ``(P, page, Hkv, D)`` pool's (1, D)
head slice is not.  Host payloads stay token-major ``(tokens, Hkv, D)``;
`pages_to_tokens` is the one conversion.

Dynamic-masking + DMA-elision contract (what shape-bucketed dispatch leans
on): ctx_lens and block tables are traced data, never static shapes, so one
compiled kernel serves every context length that fits a (B, maxp) bucket.
Two mechanisms keep the padded page walk from costing real bandwidth:

1. COMPUTE masking — a grid step whose page begins at or beyond
   ``min(ctx_lens[b], q_hi + 1)`` (the lane's context end / the q block's
   causal horizon) contributes nothing: ``valid > 0`` gates the whole body,
   so a batch row padded with ctx_len = 0 finishes as zeros and table
   columns beyond a row's ctx never leak other sessions' KV into the
   output.

2. DMA ELISION — the K/V BlockSpec index maps *clamp* the page coordinate
   to the lane's last relevant page (per-lane page counts ride
   scalar-prefetch SMEM; the causal horizon is derived from q_offsets in
   the index map itself), so every irrelevant grid step re-maps to the
   block index the pipeline already holds in VMEM.  Pallas skips the copy
   when consecutive grid steps' index maps agree, so a lane's page walk
   issues exactly ``ceil(min(ctx, horizon) / page)`` K/V tile fetches no
   matter how wide the shared ``maxp`` bucket is — the bucket costs grid
   steps, not HBM bandwidth.

Table-padding invariant: callers pad block-table columns beyond a row's
own pages with the row's LAST VALID page id (``PagedAllocator.block_table``
does this; rows with no pages pad with 0).  Padded columns are never read
by the clamped index maps and never unmasked by compute, but repeating the
last id keeps the index-map result constant across the tail of the walk so
the elision actually fires — 0-padding would re-fetch page 0 once per lane
tail.  Anyone building tables by hand (step / scatter / fork / adopt
paths) must preserve this invariant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pages_to_tokens(pages):
    """Head-major pages ``(..., n, Hkv, page, D)`` -> token rows
    ``(..., n * page, Hkv, D)``: the gather side of every tier transfer and
    of the jnp oracles."""
    *lead, n, hkv, page, d = pages.shape
    return jnp.swapaxes(pages, -3, -2).reshape(*lead, n * page, hkv, d)


def _dim_semantics(interpret: bool, n_parallel: int):
    """Megacore partitioning hint: batch/head/q-block grid dims are
    embarrassingly parallel, only the page walk (innermost) carries the
    (m, l, acc) accumulator state.  Interpret mode ignores compiler
    params, so skip them there."""
    if interpret:
        return {}
    sem = ("parallel",) * n_parallel + ("arbitrary",)
    return dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=sem))


def _kernel(ctx_ref, npages_ref, tables_ref,  # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,              # VMEM blocks
            o_ref,                            # output block
            m_ref, l_ref, acc_ref):           # VMEM scratch
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)
    page = k_ref.shape[0]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    start = p * page
    valid = ctx - start                     # tokens valid in this page

    @pl.when(valid > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                # (G, D)
        k = k_ref[...].astype(jnp.float32)                 # (page, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s / np.sqrt(q.shape[-1])                       # (G, page)
        idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx < valid, s, -1e30)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # (G, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_ref[...] = l_prev * corr + pexp.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _chunk_body(refs, *, bq: int, G: int, quant: bool):
    """Shared body of the unified chunk kernel: the masking/accumulator
    logic lives here exactly once; ``quant`` only switches how the K/V
    tile is materialised (fp tile vs int8 shadow tile dequantized
    in-register with its per-page scale)."""
    if quant:
        (qoff_ref, ctx_ref, npages_ref, tables_ref,  # scalar prefetch
         pq_ref, ks_ref, vs_ref,                     # (SMEM)
         q_ref, k_ref, v_ref,                        # VMEM blocks
         kq_ref, vq_ref,                             # int8 shadow tiles
         o_ref,                                      # output block
         m_ref, l_ref, acc_ref) = refs               # VMEM scratch
    else:
        (qoff_ref, ctx_ref, npages_ref, tables_ref,
         q_ref, k_ref, v_ref,
         o_ref,
         m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    qi = pl.program_id(2)
    p = pl.program_id(3)
    n_pages = pl.num_programs(3)
    page = k_ref.shape[0]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    qoff = qoff_ref[b]
    start = p * page
    # a page is relevant iff it begins before BOTH the lane's context end and
    # this q block's causal horizon; ctx = 0 (padded lane) skips every page,
    # so the lane finishes as zeros without reading anyone's KV.  The kv
    # index maps clamp to the same bound, so an irrelevant step's tile DMA
    # is elided too — the tile in VMEM is stale, but never read.
    q_hi = qoff + (qi + 1) * bq - 1
    valid = jnp.minimum(ctx, q_hi + 1) - start

    @pl.when(valid > 0)
    def _compute():
        q = q_ref[0, 0].reshape(bq * G, -1).astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)                 # (page, D)
        v = v_ref[...].astype(jnp.float32)
        if quant:
            # dequantize to the pool dtype, exactly as a tier payload that
            # leaves the device re-inflates, so both paths read one value
            pid = tables_ref[b, p]
            isq = pq_ref[pid] > 0
            kd = (kq_ref[...].astype(jnp.float32) * ks_ref[pid]
                  ).astype(k_ref.dtype).astype(jnp.float32)
            vd = (vq_ref[...].astype(jnp.float32) * vs_ref[pid]
                  ).astype(v_ref.dtype).astype(jnp.float32)
            k = jnp.where(isq, kd, k)
            v = jnp.where(isq, vd, v)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s / np.sqrt(q.shape[-1])                       # (bq*G, page)
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = qoff + qi * bq + rows
        kpos = start + cols
        s = jnp.where((qpos >= kpos) & (kpos < ctx), s, -1e30)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_ref[...] = l_prev * corr + pexp.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(p == n_pages - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.reshape(bq, G, -1).astype(o_ref.dtype)


def _chunk_kernel(*refs, bq: int, G: int):
    _chunk_body(refs, bq=bq, G=G, quant=False)


def _chunk_kernel_quant(*refs, bq: int, G: int):
    """Mixed-precision variant of `_chunk_kernel`: both the fp tile and the
    int8 shadow tile of the SAME page arrive per grid step (identical index
    map), and the per-page precision bit + fp32 scales ride scalar-prefetch
    SMEM next to the block tables.  Dequant happens here, in-register —
    a quantized page never needs a re-inflation copy in HBM."""
    _chunk_body(refs, bq=bq, G=G, quant=True)


def _chunk_kv_index(bq: int, page: int):
    """Clamped K/V index map for the chunk grid (b, h, qi, p).

    Pages past the lane's own page count OR past this q block's causal
    horizon re-map to the lane's last relevant page, so consecutive grid
    steps return identical block indices and Pallas elides the tile copy.
    ``npg`` (per-lane page counts = ceil(ctx / page)) rides scalar-prefetch
    SMEM; the horizon is derived from the prefetched q_offsets.  Clamping
    never changes a RELEVANT step's fetch: valid > 0  ⟺  p < rel."""
    def kv_index(b, h, qi, p, qo, ctx, npg, tab, *_):
        horizon = (qo[b] + (qi + 1) * bq - 1) // page + 1
        rel = jnp.minimum(npg[b], horizon)
        p_eff = jnp.minimum(p, jnp.maximum(rel - 1, 0))
        return (tab[b, p_eff], h, 0, 0)
    return kv_index


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_chunk_attention_quant(q, k_pages, v_pages, kq_pages, vq_pages,
                                k_scales, v_scales, page_quant,
                                block_tables, q_offsets, ctx_lens, *,
                                bq: int = 128, interpret: bool = True):
    """`paged_chunk_attention` over mixed-precision pools: pages whose
    ``page_quant`` bit is set are read from the int8 shadow pool and
    dequantized in the kernel body with their per-page fp32 scale; the
    rest read the fp pool.  kq/vq_pages: (P, Hkv, page, D) int8;
    k/v_scales, page_quant: (P,).  Same grid/masking/DMA-elision contract
    as the all-fp kernel (the fp and int8 tiles of a page share one
    clamped index map, so both copies are elided together)."""
    B, Sq, H, D = q.shape
    P, Hkv, page, _ = k_pages.shape
    G = H // Hkv
    maxp = block_tables.shape[1]
    bq = min(bq, Sq)
    assert Sq % bq == 0
    q5 = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    npages = jnp.asarray((ctx_lens + page - 1) // page, jnp.int32)

    grid = (B, Hkv, Sq // bq, maxp)
    kern = functools.partial(_chunk_kernel_quant, bq=bq, G=G)
    kv_spec = pl.BlockSpec((None, None, page, D),
                           _chunk_kv_index(bq, page))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, G, D),
                         lambda b, h, qi, p, *_: (b, h, qi, 0, 0)),
            kv_spec, kv_spec, kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, G, D),
                               lambda b, h, qi, p, *_: (b, h, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sq, G, D), q.dtype),
        interpret=interpret,
        **_dim_semantics(interpret, 3),
    )(q_offsets, ctx_lens, npages, block_tables,
      page_quant.astype(jnp.int32), k_scales.astype(jnp.float32),
      v_scales.astype(jnp.float32), q5, k_pages, v_pages,
      kq_pages, vq_pages)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, D)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_chunk_attention(q, k_pages, v_pages, block_tables, q_offsets,
                          ctx_lens, *, bq: int = 128,
                          interpret: bool = True):
    """Mixed-batch paged attention: every lane carries a chunk of queries.

    q: (B, Sq, H, D); k/v_pages: (P, Hkv, page, D); block_tables: (B, maxp);
    q_offsets/ctx_lens: (B,) int32.  Lane b's query token i sits at absolute
    position q_offsets[b] + i and attends KV positions <= it (causal) that
    are < ctx_lens[b].  Decode is the Sq = 1 special case (q_offset =
    ctx - 1); chunked prefill sets q_offset = n_cached.  ctx_len = 0 masks a
    padded lane entirely (finishes as zeros, no KV read); padded query rows
    of a live lane (i >= its chunk length) produce garbage the caller never
    reads.  Returns (B, Sq, H, D).

    Grid: (B, Hkv, q_blocks, pages), page innermost with running (m, l, acc)
    flash accumulators in VMEM scratch; q_offsets/ctx_lens/tables are traced
    scalar-prefetch data, so one compiled kernel serves every (chunk length,
    context length) mix that pads into the same (B, Sq, maxp) bucket.  Grid
    steps past a lane's relevant pages clamp their K/V index maps to the
    last relevant page (DMA elided) and skip compute, so each lane costs
    bandwidth proportional to its OWN pages, not the bucket width — see the
    module docstring for the table-padding invariant this leans on."""
    B, Sq, H, D = q.shape
    P, Hkv, page, _ = k_pages.shape
    G = H // Hkv
    maxp = block_tables.shape[1]
    bq = min(bq, Sq)
    assert Sq % bq == 0
    q5 = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    npages = jnp.asarray((ctx_lens + page - 1) // page, jnp.int32)

    grid = (B, Hkv, Sq // bq, maxp)
    kern = functools.partial(_chunk_kernel, bq=bq, G=G)
    kv_spec = pl.BlockSpec((None, None, page, D),
                           _chunk_kv_index(bq, page))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, G, D),
                         lambda b, h, qi, p, *_: (b, h, qi, 0, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, G, D),
                               lambda b, h, qi, p, *_: (b, h, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sq, G, D), q.dtype),
        interpret=interpret,
        **_dim_semantics(interpret, 3),
    )(q_offsets, ctx_lens, npages, block_tables, q5, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                    *, interpret: bool = True):
    """q: (B,H,D); k/v_pages: (P,Hkv,page,D); block_tables: (B,maxp);
    ctx_lens: (B,). Returns (B,H,D).  Same DMA-elision contract as the
    chunk kernel: pages past ceil(ctx/page) re-map to the lane's last
    relevant page and their copies are elided."""
    B, H, D = q.shape
    P, Hkv, page, _ = k_pages.shape
    G = H // Hkv
    maxp = block_tables.shape[1]
    q4 = q.reshape(B, Hkv, G, D)
    npages = jnp.asarray((ctx_lens + page - 1) // page, jnp.int32)

    def kv_index(b, h, p, ctx, npg, tab):
        p_eff = jnp.minimum(p, jnp.maximum(npg[b] - 1, 0))
        return (tab[b, p_eff], h, 0, 0)

    grid = (B, Hkv, maxp)
    kv_spec = pl.BlockSpec((None, None, page, D), kv_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, p, *_: (b, h, 0, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, p, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        **_dim_semantics(interpret, 2),
    )(ctx_lens, npages, block_tables, q4, k_pages, v_pages)
    return out.reshape(B, H, D)
