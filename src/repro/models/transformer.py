"""Dense decoder-only transformer (llama/qwen/yi/minicpm families) and the
phi-3-vision backbone (same block; precomputed patch embeddings prepended).

Layer stacks are scanned with stacked parameters (L, ...) — keeps HLO small,
enables layerwise KV streaming (the SYMPHONY node manager moves KV tier-wise
per layer), and matches how the tiered KV store addresses cache slices.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeCell
from repro.distributed import hints
from repro.models import layers as L


class DenseLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        # fused paged serving step, jitted lazily.  jit compiles exactly
        # once per distinct (arg shapes/dtypes, static kwargs, shardings)
        # signature, so recording the signatures we dispatch gives an exact
        # compile census without reaching into jit internals (see
        # paged_compile_counts).  The jit caches are keyed by the pool
        # sharding (None = single-device): one model instance can serve
        # backends on different meshes — a tp=2 and a tp=4 node, or a
        # meshed node next to an unsharded one — without either evicting
        # the other's compiled steps or colliding in the census.
        self._step_jits: Dict = {}
        self._scatter_jits: Dict = {}
        self._fork_jits: Dict = {}
        self._fork_quant_jits: Dict = {}
        self._compress_jits: Dict = {}
        self._compile_keys = dict(step=set(), scatter=set(), fork=set(),
                                  compress=set())

    # -- parameters ---------------------------------------------------------

    def init(self, rng) -> Dict:
        c, dt = self.cfg, self.dtype
        n = c.n_layers
        ks = jax.random.split(rng, 16)

        def stack(key, shape, scale=None):
            return L.dense_init(key, (n,) + shape, dt, scale)

        p = dict(
            emb=L.dense_init(ks[0], (c.padded_vocab, c.d_model), dt, 0.02),
            ln_f=jnp.ones((c.d_model,), dt),
            blocks=dict(
                ln1=jnp.ones((n, c.d_model), dt),
                ln2=jnp.ones((n, c.d_model), dt),
                wq=stack(ks[1], (c.d_model, c.q_dim)),
                wk=stack(ks[2], (c.d_model, c.kv_dim)),
                wv=stack(ks[3], (c.d_model, c.kv_dim)),
                wo=stack(ks[4], (c.q_dim, c.d_model)),
                w1=stack(ks[5], (c.d_model, c.d_ff)),
                w3=stack(ks[6], (c.d_model, c.d_ff)),
                w2=stack(ks[7], (c.d_ff, c.d_model)),
            ),
        )
        if not c.tie_embeddings:
            p["lm_head"] = L.dense_init(ks[8], (c.padded_vocab, c.d_model), dt, 0.02)
        if c.qk_norm:
            p["blocks"]["qn"] = jnp.ones((n, c.d_head), dt)
            p["blocks"]["kn"] = jnp.ones((n, c.d_head), dt)
        if c.family == "vlm":
            p["patch_proj"] = L.dense_init(ks[9], (c.d_frontend, c.d_model), dt)
        return p

    def param_count(self) -> int:
        c = self.cfg
        per_layer = (c.d_model * (c.q_dim + 2 * c.kv_dim) + c.q_dim * c.d_model
                     + 3 * c.d_model * c.d_ff + 2 * c.d_model)
        emb = c.vocab * c.d_model * (1 if c.tie_embeddings else 2)
        extra = c.d_frontend * c.d_model if c.family == "vlm" else 0
        return c.n_layers * per_layer + emb + c.d_model + extra

    def active_param_count(self) -> int:
        return self.param_count()

    # -- blocks -------------------------------------------------------------

    def _attn(self, x, w, *, positions, cache_kv=None, cache_len=None,
              prefix_kv=None, q_offset=0):
        """Returns (attn_out, new_kv). Modes:
        - training/prefill: full-sequence flash attention (+optional prefix)
        - decode: cache_kv given, single new position per sequence
        """
        c = self.cfg
        B, S, _ = x.shape
        q = (x @ w["wq"]).reshape(B, S, c.n_heads, c.d_head)
        k = (x @ w["wk"]).reshape(B, S, c.n_kv_heads, c.d_head)
        v = (x @ w["wv"]).reshape(B, S, c.n_kv_heads, c.d_head)
        if c.qk_norm:
            q = L.rms_norm(q, w["qn"], c.norm_eps)
            k = L.rms_norm(k, w["kn"], c.norm_eps)
        q = L.apply_rope(q, positions, c.rope_theta)
        k = L.apply_rope(k, positions, c.rope_theta)

        if cache_kv is not None:          # decode: S == 1, cache (B,H,S,D)
            k_cache, v_cache = cache_kv
            bi = jnp.arange(B)[:, None]
            hi = jnp.arange(c.n_kv_heads)[None, :]
            k_cache = k_cache.at[bi, hi, cache_len[:, None]].set(
                k[:, 0].transpose(0, 1, 2))
            v_cache = v_cache.at[bi, hi, cache_len[:, None]].set(v[:, 0])
            o = L.decode_attention(q, k_cache, v_cache, cache_len + 1,
                                   window=c.sliding_window, layout="bhsd")
            return o.reshape(B, S, -1) @ w["wo"], (k_cache, v_cache)

        if prefix_kv is not None:         # continuation prefill (multi-turn)
            pk, pv = prefix_kv
            k = jnp.concatenate([pk, k], axis=1)
            v = jnp.concatenate([pv, v], axis=1)
        # ragged-head archs (36 heads, TP=16): force (padded) head sharding,
        # else GSPMD replicates the attention streams over `model` (SSPerf it.8)
        q = hints.shard(q, "attn_heads")
        k = hints.shard(k, "attn_heads")
        v = hints.shard(v, "attn_heads")
        o = L.flash_attention(q, k, v, causal=True, q_offset=q_offset,
                              window=c.sliding_window)
        return o.reshape(B, S, -1) @ w["wo"], (k, v)

    def _ffn(self, x, w):
        """Returns (ffn_out, aux_loss)."""
        return L.swiglu(x, w["w1"], w["w3"], w["w2"]), jnp.float32(0.0)

    def _block(self, x, w, *, positions, cache_kv=None, cache_len=None,
               prefix_kv=None, q_offset=0):
        c = self.cfg
        a, new_kv = self._attn(L.rms_norm(x, w["ln1"], c.norm_eps), w,
                               positions=positions, cache_kv=cache_kv,
                               cache_len=cache_len, prefix_kv=prefix_kv,
                               q_offset=q_offset)
        x = x + a
        h, aux = self._ffn(L.rms_norm(x, w["ln2"], c.norm_eps), w)
        return x + h, new_kv, aux

    # -- embedding / unembedding --------------------------------------------

    def _embed(self, params, tokens, patches=None):
        x = params["emb"][tokens]
        if patches is not None:
            pe = (patches.astype(self.dtype) @ params["patch_proj"])
            x = jnp.concatenate([pe, x], axis=1)
        return x

    def _unembed(self, params, x):
        head = params["emb"] if self.cfg.tie_embeddings else params["lm_head"]
        return jnp.einsum("...d,vd->...v", x, head)

    # -- public API ----------------------------------------------------------

    def loss(self, params, batch) -> jax.Array:
        c = self.cfg
        tokens, targets = batch["tokens"], batch["targets"]
        patches = batch.get("patches")
        x = hints.shard(self._embed(params, tokens, patches), "act")
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]

        def block(x, w):
            x = hints.shard(x, "residual")
            x, _, aux = self._block(x, w, positions=positions)
            return x, aux
        block = jax.checkpoint(block)

        def body(x, w):
            return block(x, w)
        x, auxs = jax.lax.scan(body, x, params["blocks"])
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        if patches is not None:          # loss over text positions only
            x = x[:, patches.shape[1]:]
        logits = hints.shard(self._unembed(params, x), "logits")
        xent = L.softmax_xent(logits, targets, batch.get("loss_mask"))
        return xent + auxs.sum()

    def init_cache(self, batch: int, seq_len: int) -> Dict:
        """Head-major (L, B, Hkv, S, D): per-head (S, D) tiles contiguous, so
        the decode read path needs no transpose-copies (SSPerf iteration 3)."""
        c = self.cfg
        kv = lambda: jnp.zeros(
            (c.n_layers, batch, c.n_kv_heads, seq_len, c.d_head), self.dtype)
        return dict(k=kv(), v=kv(), len=jnp.zeros((batch,), jnp.int32))

    def cache_seq_len(self, cache) -> int:
        return cache["k"].shape[3]

    def grow_cache(self, cache, extra: int) -> Dict:
        big = self.init_cache(cache["k"].shape[1], self.cache_seq_len(cache)
                              + extra)
        for key in ("k", "v"):
            big[key] = big[key].at[..., :cache[key].shape[3], :].set(cache[key])
        big["len"] = cache["len"]
        return big

    def prefill(self, params, tokens, patches=None):
        """Process a full prompt; returns (last_logits, cache)."""
        c = self.cfg
        x = self._embed(params, tokens, patches)
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]

        def body(x, w):
            x, (k, v), _ = self._block(x, w, positions=positions)
            return x, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
        x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = self._unembed(params, x[:, -1])
        cache = dict(k=ks, v=vs, len=jnp.full((B,), S, jnp.int32))
        return logits, cache

    def decode_step(self, params, cache, tokens):
        """One token per sequence. tokens: (B,) int32."""
        c = self.cfg
        x = self._embed(params, tokens[:, None])
        clen = cache["len"]
        positions = clen[:, None]

        def body(x, wkv):
            w, (k_c, v_c) = wkv
            x, (k_c, v_c), _ = self._block(x, w, positions=positions,
                                           cache_kv=(k_c, v_c), cache_len=clen)
            return x, (k_c, v_c)
        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"],
                                             (cache["k"], cache["v"])))
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = self._unembed(params, x[:, 0])
        return logits, dict(k=ks, v=vs, len=clen + 1)

    # -- paged entry point (RealBackend serving path) -------------------------
    #
    # Same math as prefill()/decode_step(), but the KV lives in ONE stacked
    # physical page pool (L, P, Hkv, page, D) — head-major, the layout the
    # Pallas kernel's (page, D) tiles need — addressed through block
    # tables — the layout the SYMPHONY node manager migrates between tiers,
    # and the layout that lets tier transfers move all L layers in a single
    # device<->host copy.  There is ONE entry point, `step_paged`: a MIXED
    # batch where every lane carries a (q_len, ctx_len) pair — decode lanes
    # are the q_len = 1 special case, chunked-prefill lanes carry this
    # step's slice of new prompt tokens — so one engine iteration is one
    # fused dispatch regardless of its prefill/decode composition.  The
    # layer stack is a `jax.lax.scan` over the already-stacked block weights
    # with the KV scatter, the unified paged_chunk_attention kernel, and the
    # FFN inside the scanned body.
    #
    # Every data-dependent quantity (q_offsets, ctx_lens, last_idx) is
    # traced, so the jit cache is keyed only on the SHAPE BUCKET (padded
    # lanes x padded tokens-per-step x table width) the backend dispatches
    # into — steady-state serving is recompile-free.  Padded token slots
    # scatter their KV into a caller-supplied trash slot and their outputs
    # are never read (attention rows are independent, the FFN is
    # position-wise, and logits/argmax are taken at `last_idx` only); a
    # padded lane sets ctx_len = 0 and is masked out of attention entirely.
    # The argmax stays on device so the step returns token ids without a
    # full-logits host sync.

    def _step_paged_impl(self, params, token_ids, k_pool, v_pool, tables,
                         q_offsets, ctx_lens, last_idx, slot_pages,
                         slot_offs, quant=None, *, kernel_mode, mesh=None):
        from repro.kernels import ops
        c = self.cfg
        ids = jnp.asarray(token_ids, jnp.int32)
        x = self._embed(params, ids)
        B, Sq = ids.shape
        positions = q_offsets[:, None] + jnp.arange(Sq)[None, :]

        def body(x, xs):
            if quant is None:
                w, kp, vp, table, sp, so = xs
                qt = None
            else:
                # per-layer slices of the int8 shadow pools, scales and the
                # precision bits ride the scan as read-only xs — only
                # compress_paged ever writes them
                w, kp, vp, table, sp, so, kq, vq, ks, vs, pq = xs
                qt = (kq, vq, ks, vs, pq)
            h = L.rms_norm(x, w["ln1"], c.norm_eps)
            q = (h @ w["wq"]).reshape(B, Sq, c.n_heads, c.d_head)
            k = (h @ w["wk"]).reshape(B, Sq, c.n_kv_heads, c.d_head)
            v = (h @ w["wv"]).reshape(B, Sq, c.n_kv_heads, c.d_head)
            if c.qk_norm:
                q = L.rms_norm(q, w["qn"], c.norm_eps)
                k = L.rms_norm(k, w["kn"], c.norm_eps)
            q = L.apply_rope(q, positions, c.rope_theta)
            k = L.apply_rope(k, positions, c.rope_theta)
            kp = kp.at[sp, :, so].set(k.astype(kp.dtype))
            vp = vp.at[sp, :, so].set(v.astype(vp.dtype))
            o = ops.paged_chunk_attention(q, kp, vp, table, q_offsets,
                                          ctx_lens, mode=kernel_mode,
                                          quant=qt, mesh=mesh)
            x = x + o.reshape(B, Sq, -1) @ w["wo"]
            h2 = L.rms_norm(x, w["ln2"], c.norm_eps)
            x = x + L.swiglu(h2, w["w1"], w["w3"], w["w2"])
            return x, (kp, vp)

        xs = (params["blocks"], k_pool, v_pool, tables,
              slot_pages, slot_offs)
        if quant is not None:
            xs = xs + tuple(quant)
        x, (k_pool, v_pool) = jax.lax.scan(body, x, xs)
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = self._unembed(params, x[jnp.arange(B), last_idx])
        toks = jnp.argmax(logits[:, :c.vocab], axis=-1).astype(jnp.int32)
        return toks, logits, k_pool, v_pool

    @staticmethod
    def _mesh_sig(pool_sharding):
        """Census/jit-cache key component for a device-mesh placement:
        (axis-name, size) pairs plus the pool PartitionSpec.  None (the
        single-device path) keys separately from every mesh, and two mesh
        shapes with identical bucket signatures no longer collide in the
        bounded-recompilation census."""
        if pool_sharding is None:
            return None
        return (tuple(pool_sharding.mesh.shape.items()),
                str(pool_sharding.spec))

    def step_paged(self, params, token_ids, k_pool, v_pool, tables,
                   q_offsets, ctx_lens, last_idx, slot_pages, slot_offs,
                   quant=None, kernel_mode: str = "auto",
                   pool_sharding=None):
        """ONE fused mixed-batch serving iteration over paged KV.

        token_ids: (B, Sq) int32, bucket-padded both ways.  Lane b's first
          q_len[b] = ctx_lens[b] - q_offsets[b] slots are this step's real
          tokens (a decode lane's pending token, or a chunk of prompt);
          their KV lands at absolute positions [q_offsets[b], ctx_lens[b]).
        k_pool/v_pool: (L, P, Hkv, page, D) stacked head-major pools.
        tables: (L, B, T) int32 block tables.  Columns beyond a lane's own
          pages must repeat the lane's LAST VALID page id (what
          ``PagedAllocator.block_table`` emits) — padded columns are fully
          compute-masked either way, but the constant tail is what lets
          the attention kernel's clamped index maps elide the padded
          walk's tile DMAs.
        q_offsets: (B,) traced int32 — tokens whose KV is already written.
        ctx_lens: (B,) traced int32 — valid tokens incl. this step's chunk
          (0 masks a padded lane out of attention entirely).
        last_idx: (B,) traced int32 — index of the lane's last real token,
          where logits/argmax are read (0 for padded lanes).
        slot_pages/slot_offs: (L, B, Sq) destination of each token's KV;
          padded slots must point at a trash slot.
        quant: optional mixed-precision shadow state — (kq_pool, vq_pool,
          k_scale (L, P), v_scale (L, P), page_quant (L, P) int32); pages
          whose bit is set dequantize from the int8 pool inside the
          attention kernel.  None keeps the all-fp signature (and its jit
          cache entries) bit-identical to a node that never quantizes.
        pool_sharding: NamedSharding of the stacked pools on a device mesh
          (None = single device).  The scan carry's pool shardings are
          PINNED to it via out_shardings so donation still aliases input to
          output on every mesh; token ids and logits are pinned replicated
          (both are host-fetched every step).  A Pallas kernel mode runs
          the attention kernel per kv-head shard under `jax.shard_map` on
          this sharding's mesh.
        Returns (argmax token ids (B,), logits (B, V), k_pool, v_pool).
        """
        key, jit_fn = self._step_jit(pool_sharding)
        args = (params, token_ids, k_pool, v_pool, tables,
                q_offsets, ctx_lens, last_idx, slot_pages, slot_offs,
                quant)
        self._compile_keys["step"].add(
            (key,) + self._shape_sig(args, kernel_mode))
        return jit_fn(*args, kernel_mode=kernel_mode)

    def _step_jit(self, pool_sharding):
        key = self._mesh_sig(pool_sharding)
        jit_fn = self._step_jits.get(key)
        if jit_fn is None:
            # donate the pools: the backend unconditionally replaces its
            # references with the returned pools, so input and output may
            # alias (per shard, on a mesh)
            kw = dict(static_argnames=("kernel_mode",),
                      donate_argnums=(2, 3))
            impl = self._step_paged_impl
            if pool_sharding is not None:
                repl = jax.sharding.NamedSharding(
                    pool_sharding.mesh, jax.sharding.PartitionSpec())
                kw["out_shardings"] = (repl, repl, pool_sharding,
                                       pool_sharding)
                impl = functools.partial(impl, mesh=pool_sharding.mesh)
            jit_fn = self._step_jits[key] = jax.jit(impl, **kw)
        return key, jit_fn

    def lower_step_paged(self, *args, kernel_mode: str,
                         pool_sharding=None):
        """Lower the fused step for `step_paged`'s positional args (arrays
        or ShapeDtypeStructs) without running it or touching the compile
        census.  ``.compile().memory_analysis()`` of the result is the
        step's device memory at that shape bucket."""
        return self._step_jit(pool_sharding)[1].lower(
            *args, kernel_mode=kernel_mode)

    @staticmethod
    def _scatter_paged_impl(k_pool, v_pool, layer_ids, pages, offs, ks, vs):
        return (k_pool.at[layer_ids, pages, :, offs].set(ks),
                v_pool.at[layer_ids, pages, :, offs].set(vs))

    def scatter_paged(self, k_pool, v_pool, layer_ids, pages, offs, ks, vs,
                      pool_sharding=None):
        """Swap-in / prefetch scatter of host-staged KV into the stacked
        pools.  Donating the pools is what keeps peak device memory at 1x
        per side — an undonated `.at[].set()` transiently materializes a
        second full pool.  Shapes must be bucket-padded by the caller (pad
        rows/slots aimed at the trash page) so each scatter compiles once
        per (rows, tokens) bucket, censused under the "scatter" key.

        layer_ids: (G, 1) int32; pages/offs: (G, n) int32 destinations;
        ks/vs: (G, n, Hkv, D) payloads.  Returns (k_pool, v_pool)."""
        key = self._mesh_sig(pool_sharding)
        jit_fn = self._scatter_jits.get(key)
        if jit_fn is None:
            kw = dict(donate_argnums=(0, 1))
            if pool_sharding is not None:
                kw["out_shardings"] = (pool_sharding, pool_sharding)
            jit_fn = self._scatter_jits[key] = jax.jit(
                self._scatter_paged_impl, **kw)
        args = (k_pool, v_pool, layer_ids, pages, offs, ks, vs)
        self._compile_keys["scatter"].add(
            (key,) + self._shape_sig(args, "scatter"))
        return jit_fn(*args)

    @staticmethod
    def _fork_paged_impl(k_pool, v_pool, layer_ids, src, dst):
        return (k_pool.at[layer_ids, dst].set(k_pool[layer_ids, src]),
                v_pool.at[layer_ids, dst].set(v_pool[layer_ids, src]))

    def fork_paged(self, k_pool, v_pool, layer_ids, src, dst,
                   pool_sharding=None):
        """Copy-on-write page fork: device-side copy of whole pages within
        the stacked pools (pool[l, dst] <- pool[l, src]), one fused donating
        dispatch for a whole batch of (layer, src, dst) triples.  The
        backend calls this when a lane's first write of a step lands inside
        a page other sequences still read — the writer gets a private copy,
        readers keep the original.  Pad rows must point src == dst == the
        trash page (a harmless self-copy) so each fork compiles once per
        row-count bucket, censused under the "fork" key.

        layer_ids/src/dst: (F,) int32.  Returns (k_pool, v_pool)."""
        key = self._mesh_sig(pool_sharding)
        jit_fn = self._fork_jits.get(key)
        if jit_fn is None:
            kw = dict(donate_argnums=(0, 1))
            if pool_sharding is not None:
                kw["out_shardings"] = (pool_sharding, pool_sharding)
            jit_fn = self._fork_jits[key] = jax.jit(
                self._fork_paged_impl, **kw)
        args = (k_pool, v_pool, layer_ids, src, dst)
        self._compile_keys["fork"].add(
            (key,) + self._shape_sig(args, "fork"))
        return jit_fn(*args)

    @staticmethod
    def _fork_paged_quant_impl(k_pool, v_pool, kq_pool, vq_pool,
                               k_scale, v_scale, layer_ids, src, dst, srcq):
        isq = srcq[:, None, None, None] > 0
        kd = kq_pool[layer_ids, src].astype(jnp.float32) \
            * k_scale[layer_ids, src][:, None, None, None]
        vd = vq_pool[layer_ids, src].astype(jnp.float32) \
            * v_scale[layer_ids, src][:, None, None, None]
        ksrc = jnp.where(isq, kd.astype(k_pool.dtype),
                         k_pool[layer_ids, src])
        vsrc = jnp.where(isq, vd.astype(v_pool.dtype),
                         v_pool[layer_ids, src])
        return (k_pool.at[layer_ids, dst].set(ksrc),
                v_pool.at[layer_ids, dst].set(vsrc))

    def fork_paged_quant(self, k_pool, v_pool, kq_pool, vq_pool, k_scale,
                         v_scale, layer_ids, src, dst, srcq,
                         pool_sharding=None):
        """`fork_paged` generalized over mixed-precision sources: rows with
        ``srcq`` set RE-MATERIALIZE full precision from the int8 shadow pool
        (dequant with the source page's scale) instead of copying the stale
        fp bytes.  Two shapes ride the same batch:

        * CoW fork of a quantized donor page (src != dst): the writer's
          private copy comes up fp, the donor's int8 page is untouched;
        * dequant-in-place (src == dst): a sole holder about to write
          mid-page inflates its own page back to fp — 0 new pages, the
          caller clears the allocator's precision bit.

        Pad rows point src == dst == trash with srcq = 0.  Censused under
        the "fork" key (the quant signature differs from the all-fp fork's,
        so the census still counts each bucket once)."""
        key = self._mesh_sig(pool_sharding)
        jit_fn = self._fork_quant_jits.get(key)
        if jit_fn is None:
            kw = dict(donate_argnums=(0, 1))
            if pool_sharding is not None:
                kw["out_shardings"] = (pool_sharding, pool_sharding)
            jit_fn = self._fork_quant_jits[key] = jax.jit(
                self._fork_paged_quant_impl, **kw)
        args = (k_pool, v_pool, kq_pool, vq_pool, k_scale, v_scale,
                layer_ids, src, dst, srcq)
        self._compile_keys["fork"].add(
            (key,) + self._shape_sig(args, "fork_quant"))
        return jit_fn(*args)

    @staticmethod
    def _compress_paged_impl(k_pool, v_pool, kq_pool, vq_pool, k_scale,
                             v_scale, layer_ids, pages):
        from repro.kernels.quant import quantize_int8
        kq, ks = quantize_int8(k_pool[layer_ids, pages], axis=(1, 2, 3))
        vq, vs = quantize_int8(v_pool[layer_ids, pages], axis=(1, 2, 3))
        return (kq_pool.at[layer_ids, pages].set(kq),
                vq_pool.at[layer_ids, pages].set(vq),
                k_scale.at[layer_ids, pages].set(ks),
                v_scale.at[layer_ids, pages].set(vs))

    def compress_paged(self, k_pool, v_pool, kq_pool, vq_pool, k_scale,
                       v_scale, layer_ids, pages, pool_sharding=None):
        """Quantize a batch of cold pages into the int8 shadow pools: one
        fused donating dispatch per (row-count) bucket writes
        ``kq/vq_pool[l, p]`` and the per-page fp32 scales for every
        (layer, page) row.  The fp pools are read-only (their bytes become
        dead capacity the moment the allocator's precision bit flips); the
        shadow pools and scale arrays are donated.  Pad rows must point at
        (layer 0, trash page).  Censused under the "compress" key.

        layer_ids/pages: (R,) int32.  Returns (kq_pool, vq_pool, k_scale,
        v_scale)."""
        key = self._mesh_sig(pool_sharding)
        jit_fn = self._compress_jits.get(key)
        if jit_fn is None:
            kw = dict(donate_argnums=(2, 3, 4, 5))
            if pool_sharding is not None:
                repl = jax.sharding.NamedSharding(
                    pool_sharding.mesh, jax.sharding.PartitionSpec())
                kw["out_shardings"] = (pool_sharding, pool_sharding,
                                       repl, repl)
            jit_fn = self._compress_jits[key] = jax.jit(
                self._compress_paged_impl, **kw)
        args = (k_pool, v_pool, kq_pool, vq_pool, k_scale, v_scale,
                layer_ids, pages)
        self._compile_keys["compress"].add(
            (key,) + self._shape_sig(args, "compress"))
        return jit_fn(*args)

    @staticmethod
    def _shape_sig(args, kernel_mode: str):
        """jit cache key stand-in: shapes + dtypes of every array leaf plus
        the static kwarg — distinct signatures == distinct compilations."""
        return (kernel_mode,) + tuple(
            (tuple(a.shape), str(getattr(a, "dtype", type(a))))
            for a in jax.tree.leaves(args) if hasattr(a, "shape"))

    def paged_compile_counts(self) -> Dict[str, int]:
        """Number of distinct XLA compilations of the fused serving step
        (one per (lanes, tokens-per-step, table width) shape bucket; the
        recompile-free invariant's observable)."""
        return {k: len(v) for k, v in self._compile_keys.items()}

    # -- dry-run specs --------------------------------------------------------

    def input_specs(self, cell: ShapeCell) -> Dict:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        c = self.cfg
        B, S = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        if c.family == "vlm":
            P = c.n_patches
            text = S - P
            if cell.kind == "train":
                return dict(tokens=jax.ShapeDtypeStruct((B, text), i32),
                            targets=jax.ShapeDtypeStruct((B, text), i32),
                            patches=jax.ShapeDtypeStruct((B, P, c.d_frontend),
                                                         jnp.bfloat16))
            if cell.kind == "prefill":
                return dict(tokens=jax.ShapeDtypeStruct((B, text), i32),
                            patches=jax.ShapeDtypeStruct((B, P, c.d_frontend),
                                                         jnp.bfloat16))
        if cell.kind in ("train",):
            return dict(tokens=jax.ShapeDtypeStruct((B, S), i32),
                        targets=jax.ShapeDtypeStruct((B, S), i32))
        if cell.kind == "prefill":
            return dict(tokens=jax.ShapeDtypeStruct((B, S), i32))
        # decode: one new token against an S-long cache
        cache = jax.eval_shape(lambda: self.init_cache(B, S))
        return dict(cache=cache, tokens=jax.ShapeDtypeStruct((B,), i32))
