"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import pages_to_tokens


def paged_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens):
    """Decode attention over paged KV.

    q:            (B, H, D)
    k/v_pages:    (P, Hkv, page_size, D)  — global page pool, head-major
    block_tables: (B, max_pages) int32    — page ids per sequence
    ctx_lens:     (B,) int32              — valid tokens per sequence
    returns:      (B, H, D)

    Relevance contract (mirrors the Pallas kernel's DMA elision): only
    positions < ctx_lens[b] — i.e. the lane's first ceil(ctx/page) table
    columns — ever reach the softmax.  Table columns beyond that are
    masked whatever they hold, so the last-valid-page padding the callers
    use (which duplicates a page id across the row's tail) is exactly as
    correct here as 0-padding: duplicated gather rows land at kpos >= ctx
    and are dropped by the mask.
    """
    B, H, D = q.shape
    P, Hkv, page, _ = k_pages.shape
    G = H // Hkv
    maxp = block_tables.shape[1]
    S = maxp * page

    # gather each sequence's pages into dense (B, S, Hkv, D)
    k = pages_to_tokens(k_pages[block_tables])
    v = pages_to_tokens(v_pages[block_tables])
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32))
    s = s / np.sqrt(D)
    mask = jnp.arange(S)[None] < ctx_lens[:, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def paged_kv_write_ref(k_pages, v_pages, k, v, slot_pages, slot_offs, *,
                       layer):
    """The step's KV write as a plain scatter: row (b, i) of k/v (B, Sq,
    Hkv, D) lands at [layer, slot_pages[b, i], head, slot_offs[b, i]] of
    the stacked (L, P+1, Hkv, page, D) pools, one row per (token, kv
    head); padded rows' slots aim at the trash page.  Returns the pools.
    The window is D alone, the pools' minor dim, so XLA keeps their
    layout (an (Hkv, D) window makes it relayout the whole pool around
    the write)."""
    heads = jnp.arange(k.shape[2])
    at = (layer, slot_pages[..., None], heads, slot_offs[..., None])
    return (k_pages.at[at].set(k.astype(k_pages.dtype)),
            v_pages.at[at].set(v.astype(v_pages.dtype)))


def paged_chunk_attention_ref(q, k_pages, v_pages, block_tables,
                              q_offsets, ctx_lens, *, layer, quant=None):
    """Mixed-batch paged attention: each lane is a chunk of queries.

    q:            (B, Sq, H, D) — lane b's token i at position q_offsets[b]+i
    k/v_pages:    (L, P, Hkv, page_size, D) stacked pools
    layer:        int32 scalar — the layer of the pools that is read
    block_tables: (B, max_pages) int32, that layer's
    q_offsets:    (B,) int32 — cached context before the chunk
    ctx_lens:     (B,) int32 — total valid KV incl. the chunk (0 = padded
                  lane, output zeroed to match the kernel's page skip)
    quant:        optional (kq_pages, vq_pages, k_scales, v_scales,
                  page_quant) — stacked int8 shadow pools
                  (L, P, Hkv, page, D) read at ``layer``, the layer's
                  per-page fp32 scales (P,), and its per-page precision
                  bit (P,) int32; pages flagged quantized are dequantized
                  from the shadow pool to the pool dtype, the rest read
                  full precision
    returns:      (B, Sq, H, D)

    Relevance contract (mirrors the kernel's clamped index maps): a KV
    position contributes iff it is causally visible (qpos >= kpos) AND
    < ctx_lens[b] — the same bound the kernel's per-lane page-count clamp
    enforces at DMA granularity.  Table columns past ceil(ctx/page) are
    therefore free to repeat the lane's last valid page id (the padding
    the serving step emits so the kernel's copy elision fires): the
    duplicated rows sit at kpos >= ctx and never survive the mask.
    """
    B, Sq, H, D = q.shape
    _, _, Hkv, page, _ = k_pages.shape
    G = H // Hkv
    maxp = block_tables.shape[1]
    S = maxp * page

    k = k_pages[layer, block_tables]
    v = v_pages[layer, block_tables]
    if quant is not None:
        kq_pages, vq_pages, k_scales, v_scales, page_quant = quant
        isq = (page_quant[block_tables] > 0)[..., None, None, None]
        kd = (kq_pages[layer, block_tables].astype(jnp.float32)
              * k_scales[block_tables][..., None, None, None]
              ).astype(k.dtype)
        vd = (vq_pages[layer, block_tables].astype(jnp.float32)
              * v_scales[block_tables][..., None, None, None]
              ).astype(v.dtype)
        k = jnp.where(isq, kd, k)
        v = jnp.where(isq, vd, v)
    k = pages_to_tokens(k)
    v = pages_to_tokens(v)
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    s = s / np.sqrt(D)
    qpos = q_offsets[:, None] + jnp.arange(Sq)[None, :]          # (B, Sq)
    kpos = jnp.arange(S)
    mask = (qpos[:, :, None] >= kpos[None, None, :]) \
        & (kpos[None, None, :] < ctx_lens[:, None, None])
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    o = jnp.where(ctx_lens[:, None, None, None, None] > 0, o, 0.0)
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def flash_prefill_ref(q, k, v, q_offset=0):
    """Causal attention with cached prefix.

    q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D), Skv >= Sq;
    q token i sits at absolute position q_offset + i. returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32)) / np.sqrt(D)
    qpos = q_offset + jnp.arange(Sq)
    mask = qpos[:, None] >= jnp.arange(Skv)[None, :]
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def ssd_scan_ref(x, dA, Bm, Cm):
    """Sequential SSD/Mamba2 recurrence oracle (token-by-token, fp32).

    x:  (B, S, H, P)   inputs (already dt-scaled)
    dA: (B, S, H)      per-step log decay (negative)
    Bm: (B, S, H, N)   input projections  (per-head; caller broadcasts groups)
    Cm: (B, S, H, N)   output projections
    returns (y: (B, S, H, P), state: (B, H, N, P))
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    h = jnp.zeros((B, H, N, P), jnp.float32)
    ys = []
    for t in range(S):
        h = (h * jnp.exp(dA[:, t]).astype(jnp.float32)[..., None, None]
             + jnp.einsum("bhn,bhp->bhnp", Bm[:, t].astype(jnp.float32),
                          x[:, t].astype(jnp.float32)))
        ys.append(jnp.einsum("bhn,bhnp->bhp", Cm[:, t].astype(jnp.float32), h))
    return jnp.stack(ys, axis=1), h
