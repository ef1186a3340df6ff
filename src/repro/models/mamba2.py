"""Zamba2-style hybrid: Mamba2 (SSD) backbone + shared attention blocks.

Structure (see configs/zamba2_2_7b.py): ``n_layers`` Mamba2 layers organized
in groups of ``shared_every``; before each group a *shared* attention+MLP
block runs (parameters shared across applications, alternating between
``n_shared_blocks`` parameter sets — Zamba2's ABAB pattern).  The shared
blocks use a sliding window at long context (sub-quadratic; DESIGN.md §6).

SSD scan follows the chunked algorithm of Mamba-2 (arXiv:2405.21060),
computed in fp32, scanned over chunks (trip-count visible to the roofline
parser).  Simplifications vs the HF checkpoint, documented in DESIGN.md:
separate (wz,wxbc,wdt) projections instead of one fused in_proj; shared
block attends over x (no concat-with-embedding LoRA adapters).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeCell
from repro.distributed import hints
from repro.models import layers as L


class Zamba2LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        s = cfg.ssm
        self.d_inner = s.expand * cfg.d_model
        self.nh = self.d_inner // s.head_dim          # SSD heads
        self.conv_dim = self.d_inner + 2 * s.n_groups * s.d_state
        assert cfg.n_layers % cfg.shared_every == 0
        self.n_groups_outer = cfg.n_layers // cfg.shared_every
        # family "mamba2" is the pure-SSM backbone: same mamba stack, no
        # shared attention blocks (shared_every only sets scan-group width)
        self.has_attn = cfg.family == "hybrid"
        # slot-pool serving entry point (StateBackend), jitted lazily with
        # an exact compile census — mirrors DenseLM's paged machinery
        self.state_pool_names = ("conv", "ssm")
        self._slots_jit = None
        self._slot_scatter_jit = None
        self._kv_scatter_jit = None
        self._compile_keys = dict(slots=set(), scatter=set())

    # -- params --------------------------------------------------------------

    def init(self, rng) -> Dict:
        c, dt = self.cfg, self.dtype
        s = c.ssm
        nl, ng = c.n_layers, self.n_groups_outer
        ks = jax.random.split(rng, 24)

        def stack(key, shape, scale=None, n=nl):
            return L.dense_init(key, (n,) + shape, dt, scale)

        mamba = dict(
            ln=jnp.ones((nl, c.d_model), dt),
            wz=stack(ks[0], (c.d_model, self.d_inner)),
            wxbc=stack(ks[1], (c.d_model, self.conv_dim)),
            wdt=stack(ks[2], (c.d_model, self.nh)),
            conv_w=stack(ks[3], (self.conv_dim, s.d_conv), 0.2),
            a_log=jnp.tile(jnp.log(jnp.arange(1, self.nh + 1, dtype=jnp.float32))[None],
                           (nl, 1)),
            dt_bias=jnp.zeros((nl, self.nh), jnp.float32),
            d_skip=jnp.ones((nl, self.nh), jnp.float32),
            norm=jnp.ones((nl, self.d_inner), dt),
            wout=stack(ks[4], (self.d_inner, c.d_model)),
        )
        out = dict(
            emb=L.dense_init(ks[12], (c.padded_vocab, c.d_model), dt, 0.02),
            ln_f=jnp.ones((c.d_model,), dt),
            mamba=mamba,
            lm_head=L.dense_init(ks[13], (c.padded_vocab, c.d_model), dt, 0.02),
        )
        if self.has_attn:
            nb = c.n_shared_blocks
            out["shared"] = dict(
                ln1=jnp.ones((nb, c.d_model), dt),
                ln2=jnp.ones((nb, c.d_model), dt),
                wq=stack(ks[5], (c.d_model, c.q_dim), n=nb),
                wk=stack(ks[6], (c.d_model, c.kv_dim), n=nb),
                wv=stack(ks[7], (c.d_model, c.kv_dim), n=nb),
                wo=stack(ks[8], (c.q_dim, c.d_model), n=nb),
                w1=stack(ks[9], (c.d_model, c.d_ff), n=nb),
                w3=stack(ks[10], (c.d_model, c.d_ff), n=nb),
                w2=stack(ks[11], (c.d_ff, c.d_model), n=nb),
            )
        return out

    def param_count(self) -> int:
        c, s = self.cfg, self.cfg.ssm
        per_mamba = (c.d_model * (self.d_inner + self.conv_dim + self.nh)
                     + self.conv_dim * s.d_conv + 3 * self.nh
                     + self.d_inner + self.d_inner * c.d_model + c.d_model)
        per_shared = (c.d_model * (c.q_dim + 2 * c.kv_dim) + c.q_dim * c.d_model
                      + 3 * c.d_model * c.d_ff + 2 * c.d_model) \
            if self.has_attn else 0
        nb = c.n_shared_blocks if self.has_attn else 0
        return (c.n_layers * per_mamba + nb * per_shared
                + 2 * c.vocab * c.d_model + c.d_model)

    def active_param_count(self) -> int:
        return self.param_count()

    # -- SSD core --------------------------------------------------------------

    def _ssd_scan(self, xh, dt, Bm, Cm, a_log, init_state=None):
        """Chunked SSD. xh:(B,S,H,P) dt:(B,S,H) Bm/Cm:(B,S,G,N) -> (y, state).

        Arbitrary S is handled by zero-padding up to a chunk multiple: dt=0
        at pads makes the decay exp(0)=1 and the input contribution dt*x=0,
        so padded steps are exact identities on the carried state."""
        c = self.cfg.ssm
        Bb, S, H, P = xh.shape
        G, N = Bm.shape[2], Bm.shape[3]
        Q = min(c.chunk, S)
        pad = (-S) % Q
        if pad:
            def zpad(t):
                return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            xh, dt, Bm, Cm = zpad(xh), zpad(dt), zpad(Bm), zpad(Cm)
        Sp = S + pad
        nc = Sp // Q
        A = -jnp.exp(a_log.astype(jnp.float32))            # (H,) negative
        dA = dt * A                                         # (B,S,H) log decay
        xdt = (xh.astype(jnp.float32) * dt[..., None])

        def reshape(t):
            return t.reshape((Bb, nc, Q) + t.shape[2:])
        dA_c, xdt_c = reshape(dA), reshape(xdt)
        B_c, C_c = reshape(Bm.astype(jnp.float32)), reshape(Cm.astype(jnp.float32))
        hpg = H // G                                        # heads per group

        def chunk_step(h, inp):
            dAq, xq, Bq, Cq = inp                           # (B,Q,...) for one chunk
            cs = jnp.cumsum(dAq, axis=1)                    # (B,Q,H)
            # intra-chunk: Y_d[i] = sum_{j<=i} (C_i.B_j) exp(cs_i-cs_j) xdt_j
            seg = cs[:, :, None, :] - cs[:, None, :, :]     # (B,Q,Q,H)
            causal = jnp.tril(jnp.ones((Q, Q), bool))
            seg = jnp.where(causal[None, :, :, None], seg, -1e30)  # mask pre-exp
            Ldec = jnp.exp(seg)
            cb = jnp.einsum("bign,bjgn->bijg", Cq, Bq)      # (B,Q,Q,G)
            cb = jnp.repeat(cb, hpg, axis=3)                # (B,Q,Q,H)
            Yd = jnp.einsum("bijh,bjhp->bihp", cb * Ldec, xq)
            # inter-chunk: Y_o[i] = (C_i . h_prev) * exp(cs_i)
            Ch = jnp.repeat(Cq, hpg, axis=2).reshape(Bb, Q, H, N)
            Yo = jnp.einsum("bihn,bhnp->bihp", Ch, h) * jnp.exp(cs)[..., None]
            # state update: h' = exp(cs_last) h + sum_j exp(cs_last-cs_j) B_j x_j
            wj = jnp.exp(cs[:, -1:, :] - cs)                # (B,Q,H)
            Bh = jnp.repeat(Bq, hpg, axis=2).reshape(Bb, Q, H, N)
            Snew = jnp.einsum("bjhn,bjhp->bhnp", Bh * wj[..., None], xq)
            h = h * jnp.exp(cs[:, -1, :])[..., None, None] + Snew
            return h, Yd + Yo

        h0 = (jnp.zeros((Bb, H, N, P), jnp.float32)
              if init_state is None else init_state.astype(jnp.float32))
        inp = (dA_c.transpose(1, 0, 2, 3), xdt_c.transpose(1, 0, 2, 3, 4),
               B_c.transpose(1, 0, 2, 3, 4), C_c.transpose(1, 0, 2, 3, 4))
        h, Yc = jax.lax.scan(chunk_step, h0, inp)
        y = Yc.transpose(1, 0, 2, 3, 4).reshape(Bb, Sp, H, P)[:, :S]
        return y, h

    def _mamba_layer(self, x, w, conv_state=None, ssm_state=None,
                     seq_mask=None, n_valid=None):
        """x: (B,S,D). Returns (out, (conv_state, ssm_state)).

        Three regimes, all exact:
        - fresh prefill (no states): chunked SSD, zero conv history;
        - single-token decode (S==1, states, no mask): recurrent step;
        - continued/mixed (states + seq_mask/n_valid): chunked SSD seeded
          with ``ssm_state``, conv window continued from ``conv_state``,
          per-lane padding masked by zeroing dt (identity state update) and
          conv tails read at each lane's ``n_valid`` boundary.
        """
        c, s = self.cfg, self.cfg.ssm
        B, S, D = x.shape
        xin = L.rms_norm(x, w["ln"], c.norm_eps)
        z = xin @ w["wz"]                                   # (B,S,d_inner)
        xbc = xin @ w["wxbc"]                               # (B,S,conv_dim)
        dt_raw = (xin @ w["wdt"]).astype(jnp.float32)       # (B,S,nh)

        single = conv_state is not None and S == 1 and seq_mask is None
        if single:                                           # decode fast path
            win = jnp.concatenate([conv_state, xbc.transpose(0, 2, 1)], -1)
            xbc_c = jnp.einsum("bdk,dk->bd", win, w["conv_w"])[:, None]
            new_conv = win[:, :, 1:]
        else:                                                # general chunked
            if conv_state is None:
                full = jnp.pad(xbc, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
            else:
                full = jnp.concatenate(
                    [conv_state.transpose(0, 2, 1).astype(xbc.dtype), xbc], 1)
            win = jnp.stack([full[:, i:i + S] for i in range(s.d_conv)], -1)
            xbc_c = jnp.einsum("bsdk,dk->bsd", win, w["conv_w"])
            if n_valid is None:
                new_conv = full[:, S:].transpose(0, 2, 1)   # (B,cd,k-1)
            else:
                # each lane's conv tail ends at its own valid-token boundary
                idx = n_valid[:, None] + jnp.arange(s.d_conv - 1)[None, :]
                new_conv = jnp.take_along_axis(
                    full, idx[:, :, None], axis=1).transpose(0, 2, 1)
        xbc_c = jax.nn.silu(xbc_c)

        xh = xbc_c[..., :self.d_inner].reshape(B, S, self.nh, s.head_dim)
        bc = xbc_c[..., self.d_inner:]
        Bm = bc[..., :s.n_groups * s.d_state].reshape(B, S, s.n_groups, s.d_state)
        Cm = bc[..., s.n_groups * s.d_state:].reshape(B, S, s.n_groups, s.d_state)
        dt = jax.nn.softplus(dt_raw + w["dt_bias"])
        if seq_mask is not None:
            dt = dt * seq_mask[:, :, None]    # pad steps: exact state identity

        if not single:
            xh = hints.shard(xh, "ssm_heads")      # (B,S,H,P): H -> model
            dt = hints.shard(dt, "ssm_gates")
            y, new_state = self._ssd_scan(xh, dt, Bm, Cm, w["a_log"],
                                          init_state=ssm_state)
        else:                                                # single-step decode
            A = -jnp.exp(w["a_log"].astype(jnp.float32))
            dA = jnp.exp(dt[:, 0] * A)                       # (B,H)
            hpg = self.nh // s.n_groups
            Bh = jnp.repeat(Bm[:, 0], hpg, axis=1)           # (B,H,N)
            Ch = jnp.repeat(Cm[:, 0], hpg, axis=1)
            xdt = xh[:, 0].astype(jnp.float32) * dt[:, 0, :, None]
            h0 = jnp.zeros((B, self.nh, s.d_state, s.head_dim), jnp.float32) \
                if ssm_state is None else ssm_state
            new_state = (h0 * dA[..., None, None]
                         + jnp.einsum("bhn,bhp->bhnp", Bh, xdt))
            y = jnp.einsum("bhn,bhnp->bhp", Ch, new_state)[:, None]
        y = y + xh.astype(jnp.float32) * w["d_skip"][:, None]
        y = y.reshape(B, S, self.d_inner)
        y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), w["norm"],
                       c.norm_eps)
        return x + (y @ w["wout"]).astype(x.dtype), (new_conv, new_state)

    # -- shared attention block -------------------------------------------------

    def _shared_block(self, x, w, *, positions, cache=None, cache_len=None):
        c = self.cfg
        B, S, _ = x.shape
        xn = L.rms_norm(x, w["ln1"], c.norm_eps)
        q = (xn @ w["wq"]).reshape(B, S, c.n_heads, c.d_head)
        k = (xn @ w["wk"]).reshape(B, S, c.n_kv_heads, c.d_head)
        v = (xn @ w["wv"]).reshape(B, S, c.n_kv_heads, c.d_head)
        q = L.apply_rope(q, positions, c.rope_theta)
        k = L.apply_rope(k, positions, c.rope_theta)
        W = min(c.sliding_window or S, self.cfg.max_context)
        if cache is None:
            o = L.flash_attention(q, k, v, causal=True, window=c.sliding_window)
            Wc = min(W, S)
            new_cache = (k[:, S - Wc:], v[:, S - Wc:])      # ring-aligned tail
        else:
            k_c, v_c = cache
            Wc = k_c.shape[1]
            slot = cache_len % Wc
            idx = jnp.arange(B)
            k_c = k_c.at[idx, slot].set(k[:, 0])
            v_c = v_c.at[idx, slot].set(v[:, 0])
            valid = jnp.minimum(cache_len + 1, Wc)
            o = L.decode_attention(q, k_c, v_c, valid)       # ring: all valid slots
            new_cache = (k_c, v_c)
        x = x + (o.reshape(B, S, -1) @ w["wo"])
        h = L.swiglu(L.rms_norm(x, w["ln2"], c.norm_eps), w["w1"], w["w3"], w["w2"])
        return x + h, new_cache

    # -- public API ---------------------------------------------------------------

    def _mamba_group_params(self):
        """Reshape stacked (nl, ...) mamba params to (n_outer, shared_every, ...)."""
        def r(t):
            return t.reshape((self.n_groups_outer, self.cfg.shared_every) + t.shape[1:])
        return r

    def loss(self, params, batch) -> jax.Array:
        c = self.cfg
        tokens, targets = batch["tokens"], batch["targets"]
        x = params["emb"][tokens]
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]
        r = self._mamba_group_params()
        gm = jax.tree.map(r, params["mamba"])

        def group(x, inp):
            g, wm = inp
            x = hints.shard(x, "residual")
            if self.has_attn:
                sw = jax.tree.map(lambda t: t[g % c.n_shared_blocks],
                                  params["shared"])
                x, _ = self._shared_block(x, sw, positions=positions)

            def mamba_body(x, w):
                return jax.checkpoint(
                    lambda x, w: self._mamba_layer(hints.shard(x, "residual"), w)[0])(x, w), None
            x, _ = jax.lax.scan(mamba_body, x, wm)
            return x, None

        x, _ = jax.lax.scan(group, x, (jnp.arange(self.n_groups_outer), gm))
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = hints.shard(
            jnp.einsum("bsd,vd->bsv", x, params["lm_head"]), "logits")
        return L.softmax_xent(logits, targets, batch.get("loss_mask"))

    def init_cache(self, batch: int, seq_len: int) -> Dict:
        c, s = self.cfg, self.cfg.ssm
        # pure mamba2 carries a zero-width attention ring so the cache pytree
        # structure is family-invariant (decode_step just threads it through)
        W = min(c.sliding_window or seq_len, seq_len) if self.has_attn else 0
        na = self.n_groups_outer
        return dict(
            ssm=jnp.zeros((c.n_layers, batch, self.nh, s.d_state, s.head_dim),
                          jnp.float32),
            conv=jnp.zeros((c.n_layers, batch, self.conv_dim, s.d_conv - 1),
                           self.dtype),
            attn_k=jnp.zeros((na, batch, W, c.n_kv_heads, c.d_head), self.dtype),
            attn_v=jnp.zeros((na, batch, W, c.n_kv_heads, c.d_head), self.dtype),
            len=jnp.zeros((batch,), jnp.int32),
        )

    def prefill(self, params, tokens):
        c = self.cfg
        x = params["emb"][tokens]
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]
        r = self._mamba_group_params()
        gm = jax.tree.map(r, params["mamba"])

        def group(x, inp):
            g, wm = inp
            if self.has_attn:
                sw = jax.tree.map(lambda t: t[g % c.n_shared_blocks],
                                  params["shared"])
                x, (kc, vc) = self._shared_block(x, sw, positions=positions)

            def mamba_body(x, w):
                x, (conv, ssm) = self._mamba_layer(x, w)
                return x, (conv, ssm)
            x, (convs, ssms) = jax.lax.scan(mamba_body, x, wm)
            if self.has_attn:
                return x, (kc, vc, convs, ssms)
            return x, (convs, ssms)

        x, ys = jax.lax.scan(group, x, (jnp.arange(self.n_groups_outer), gm))
        if self.has_attn:
            kcs, vcs, convs, ssms = ys
        else:
            convs, ssms = ys
            kcs = jnp.zeros((self.n_groups_outer, B, 0, c.n_kv_heads, c.d_head),
                            self.dtype)
            vcs = kcs
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = jnp.einsum("bd,vd->bv", x[:, -1], params["lm_head"])
        cache = dict(
            ssm=ssms.reshape((c.n_layers,) + ssms.shape[2:]),
            conv=convs.reshape((c.n_layers,) + convs.shape[2:]),
            attn_k=kcs, attn_v=vcs,
            len=jnp.full((B,), S, jnp.int32),
        )
        return logits, cache

    def decode_step(self, params, cache, tokens):
        c = self.cfg
        B = tokens.shape[0]
        x = params["emb"][tokens[:, None]]
        clen = cache["len"]
        positions = clen[:, None]
        r = self._mamba_group_params()
        gm = jax.tree.map(r, params["mamba"])
        ssm_g = cache["ssm"].reshape((self.n_groups_outer, c.shared_every)
                                     + cache["ssm"].shape[1:])
        conv_g = cache["conv"].reshape((self.n_groups_outer, c.shared_every)
                                       + cache["conv"].shape[1:])

        def group(x, inp):
            if self.has_attn:
                g, wm, kc, vc, ssm, conv = inp
                sw = jax.tree.map(lambda t: t[g % c.n_shared_blocks],
                                  params["shared"])
                x, (kc, vc) = self._shared_block(x, sw, positions=positions,
                                                 cache=(kc, vc), cache_len=clen)
            else:
                g, wm, ssm, conv = inp

            def mamba_body(x, wstate):
                w, cs, ss = wstate
                x, (cs, ss) = self._mamba_layer(x, w, conv_state=cs, ssm_state=ss)
                return x, (cs, ss)
            x, (convs, ssms) = jax.lax.scan(mamba_body, x, (wm, conv, ssm))
            if self.has_attn:
                return x, (kc, vc, convs, ssms)
            return x, (convs, ssms)

        xs = ((jnp.arange(self.n_groups_outer), gm,
               cache["attn_k"], cache["attn_v"], ssm_g, conv_g)
              if self.has_attn else
              (jnp.arange(self.n_groups_outer), gm, ssm_g, conv_g))
        x, ys = jax.lax.scan(group, x, xs)
        if self.has_attn:
            kcs, vcs, convs, ssms = ys
        else:
            convs, ssms = ys
            kcs, vcs = cache["attn_k"], cache["attn_v"]
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        logits = jnp.einsum("bd,vd->bv", x[:, 0], params["lm_head"])
        new_cache = dict(
            ssm=ssms.reshape(cache["ssm"].shape),
            conv=convs.reshape(cache["conv"].shape),
            attn_k=kcs, attn_v=vcs, len=clen + 1,
        )
        return logits, new_cache

    def grow_cache(self, cache: Dict, extra: int) -> Dict:
        """Grow the shared-attn ring window by ``extra`` slots.  A prefill of
        S < sliding_window tokens emits an S-wide ring; without growth the
        first decode would wrap at slot len % S == 0 and clobber live keys.
        Right after prefill the ring is unwrapped (tail at slot 0), so padding
        at the end keeps the slot arithmetic chronological.  No-op once the
        ring has reached the sliding window, and for pure-mamba configs."""
        c = self.cfg
        Wc = cache["attn_k"].shape[2]
        if Wc == 0:
            return cache
        W = min(c.sliding_window or (1 << 30), c.max_context)
        new_Wc = min(W, Wc + extra)
        if new_Wc <= Wc:
            return cache
        pad = ((0, 0), (0, 0), (0, new_Wc - Wc), (0, 0), (0, 0))
        return dict(cache, attn_k=jnp.pad(cache["attn_k"], pad),
                    attn_v=jnp.pad(cache["attn_v"], pad))

    # -- slot-pool serving (StateBackend) -----------------------------------------
    #
    # Recurrent session state lives in stacked donated pools indexed by a
    # fixed slot id (one slot per session; slot n_slots is the trash slot for
    # padded lanes), mirroring DenseLM's paged-pool machinery: lazy jit with
    # donate_argnums on the pools and an exact compile census keyed by shape
    # signature.

    def init_slot_pools(self, n_slots: int) -> Dict:
        c, s = self.cfg, self.cfg.ssm
        return dict(
            conv=jnp.zeros((c.n_layers, n_slots + 1, self.conv_dim,
                            s.d_conv - 1), self.dtype),
            ssm=jnp.zeros((c.n_layers, n_slots + 1, self.nh, s.d_state,
                           s.head_dim), jnp.float32),
        )

    def blank_state(self) -> Dict[str, np.ndarray]:
        """Host-side zero state for one session (used to reset a reused slot)."""
        c, s = self.cfg, self.cfg.ssm
        return dict(
            conv=np.zeros((c.n_layers, self.conv_dim, s.d_conv - 1),
                          self.dtype),
            ssm=np.zeros((c.n_layers, self.nh, s.d_state, s.head_dim),
                         np.float32),
        )

    def _gathered_states(self, pools, slot_idx):
        c = self.cfg
        rg = self._mamba_group_params()
        conv_g = rg(pools["conv"][:, slot_idx])   # (na, se, B, cd, k-1)
        ssm_g = rg(pools["ssm"][:, slot_idx])
        return conv_g, ssm_g

    def _scatter_states(self, pools, slot_idx, convs, ssms):
        c = self.cfg
        flat = lambda t: t.reshape((c.n_layers,) + t.shape[2:])
        return dict(
            conv=pools["conv"].at[:, slot_idx].set(
                flat(convs).astype(pools["conv"].dtype)),
            ssm=pools["ssm"].at[:, slot_idx].set(
                flat(ssms).astype(jnp.float32)),
        )

    def _step_slots_impl(self, params, token_ids, pools, slot_idx, n_valid,
                         last_idx, *, kernel_mode):
        c = self.cfg
        B, Sq = token_ids.shape
        x = params["emb"][token_ids]
        mask = (jnp.arange(Sq)[None, :] < n_valid[:, None]).astype(jnp.float32)
        conv_g, ssm_g = self._gathered_states(pools, slot_idx)
        gm = jax.tree.map(self._mamba_group_params(), params["mamba"])

        def group(x, inp):
            g, wm, conv, ssm = inp

            def mamba_body(x, wstate):
                w, cs, ss = wstate
                x, (cs, ss) = self._mamba_layer(
                    x, w, conv_state=cs, ssm_state=ss,
                    seq_mask=mask, n_valid=n_valid)
                return x, (cs, ss)
            x, (convs, ssms) = jax.lax.scan(mamba_body, x, (wm, conv, ssm))
            return x, (convs, ssms)

        x, (convs, ssms) = jax.lax.scan(
            group, x, (jnp.arange(self.n_groups_outer), gm, conv_g, ssm_g))
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        sel = x[jnp.arange(B), last_idx]
        logits = jnp.einsum("bd,vd->bv", sel, params["lm_head"])
        toks = jnp.argmax(logits[:, :c.vocab], axis=-1).astype(jnp.int32)
        return toks, logits, self._scatter_states(pools, slot_idx, convs, ssms)

    def _shared_block_paged(self, x, w, kp, vp, table, q_offsets, ctx_lens,
                            slot_pages, slot_offs, *, kernel_mode):
        """Shared attention over paged KV (full causal; exact vs the dense
        sliding-window reference while ctx <= sliding_window — DESIGN.md)."""
        from repro.kernels import ops
        c = self.cfg
        B, S, _ = x.shape
        xn = L.rms_norm(x, w["ln1"], c.norm_eps)
        q = (xn @ w["wq"]).reshape(B, S, c.n_heads, c.d_head)
        k = (xn @ w["wk"]).reshape(B, S, c.n_kv_heads, c.d_head)
        v = (xn @ w["wv"]).reshape(B, S, c.n_kv_heads, c.d_head)
        positions = q_offsets[:, None] + jnp.arange(S)[None, :]
        q = L.apply_rope(q, positions, c.rope_theta)
        k = L.apply_rope(k, positions, c.rope_theta)
        kp = kp.at[slot_pages, :, slot_offs].set(k.astype(kp.dtype))
        vp = vp.at[slot_pages, :, slot_offs].set(v.astype(vp.dtype))
        o = ops.paged_chunk_attention(q, kp[None], vp[None], table,
                                      q_offsets, ctx_lens, mode=kernel_mode,
                                      layer=0)
        x = x + (o.reshape(B, S, -1) @ w["wo"])
        h = L.swiglu(L.rms_norm(x, w["ln2"], c.norm_eps), w["w1"], w["w3"],
                     w["w2"])
        return x + h, kp, vp

    def _step_slots_hybrid_impl(self, params, token_ids, pools, slot_idx,
                                n_valid, last_idx, k_pool, v_pool, tables,
                                q_offsets, ctx_lens, slot_pages, slot_offs,
                                *, kernel_mode):
        """Hybrid step: recurrent slot pools + per-application paged KV.
        k/v pools are (na, P+1, Hkv, page, D); tables/slot_pages/slot_offs
        carry a leading (na,) axis and ride the group scan."""
        c = self.cfg
        B, Sq = token_ids.shape
        x = params["emb"][token_ids]
        mask = (jnp.arange(Sq)[None, :] < n_valid[:, None]).astype(jnp.float32)
        conv_g, ssm_g = self._gathered_states(pools, slot_idx)
        gm = jax.tree.map(self._mamba_group_params(), params["mamba"])

        def group(x, inp):
            g, wm, kp, vp, table, sp, so, conv, ssm = inp
            sw = jax.tree.map(lambda t: t[g % c.n_shared_blocks],
                              params["shared"])
            x, kp, vp = self._shared_block_paged(
                x, sw, kp, vp, table, q_offsets, ctx_lens, sp, so,
                kernel_mode=kernel_mode)

            def mamba_body(x, wstate):
                w, cs, ss = wstate
                x, (cs, ss) = self._mamba_layer(
                    x, w, conv_state=cs, ssm_state=ss,
                    seq_mask=mask, n_valid=n_valid)
                return x, (cs, ss)
            x, (convs, ssms) = jax.lax.scan(mamba_body, x, (wm, conv, ssm))
            return x, (kp, vp, convs, ssms)

        x, (kps, vps, convs, ssms) = jax.lax.scan(
            group, x, (jnp.arange(self.n_groups_outer), gm, k_pool, v_pool,
                       tables, slot_pages, slot_offs, conv_g, ssm_g))
        x = L.rms_norm(x, params["ln_f"], c.norm_eps)
        sel = x[jnp.arange(B), last_idx]
        logits = jnp.einsum("bd,vd->bv", sel, params["lm_head"])
        toks = jnp.argmax(logits[:, :c.vocab], axis=-1).astype(jnp.int32)
        pools = self._scatter_states(pools, slot_idx, convs, ssms)
        return toks, logits, pools, kps, vps

    def step_slots(self, params, token_ids, pools, slot_idx, n_valid, last_idx,
                   k_pool=None, v_pool=None, tables=None, q_offsets=None,
                   ctx_lens=None, slot_pages=None, slot_offs=None, *,
                   kernel_mode="auto"):
        if self._slots_jit is None:
            impl = (self._step_slots_hybrid_impl if self.has_attn
                    else self._step_slots_impl)
            donate = (2, 6, 7) if self.has_attn else (2,)
            self._slots_jit = jax.jit(impl, static_argnames=("kernel_mode",),
                                      donate_argnums=donate)
        args = (params, token_ids, pools, slot_idx, n_valid, last_idx)
        if self.has_attn:
            args += (k_pool, v_pool, tables, q_offsets, ctx_lens, slot_pages,
                     slot_offs)
        self._compile_keys["slots"].add(self._shape_sig(args, kernel_mode))
        return self._slots_jit(*args, kernel_mode=kernel_mode)

    def _scatter_slots_impl(self, pools, slot_idx, payload):
        return {k: pools[k].at[:, slot_idx].set(
            payload[k].astype(pools[k].dtype)) for k in pools}

    def scatter_slots(self, pools, slot_idx, payload):
        """Write per-session state blobs into slots. slot_idx: (B,);
        payload leaves: (n_layers, B, ...)."""
        if self._slot_scatter_jit is None:
            self._slot_scatter_jit = jax.jit(self._scatter_slots_impl,
                                             donate_argnums=(0,))
        self._compile_keys["scatter"].add(
            self._shape_sig((pools, slot_idx, payload), None))
        return self._slot_scatter_jit(pools, slot_idx, payload)

    @staticmethod
    def _scatter_paged_impl(k_pool, v_pool, app_ids, pages, offs, ks, vs):
        k_pool = k_pool.at[app_ids, pages, :, offs].set(
            ks.astype(k_pool.dtype))
        v_pool = v_pool.at[app_ids, pages, :, offs].set(
            vs.astype(v_pool.dtype))
        return k_pool, v_pool

    def scatter_paged(self, k_pool, v_pool, app_ids, pages, offs, ks, vs):
        if self._kv_scatter_jit is None:
            self._kv_scatter_jit = jax.jit(self._scatter_paged_impl,
                                           donate_argnums=(0, 1))
        args = (k_pool, v_pool, app_ids, pages, offs, ks, vs)
        self._compile_keys["scatter"].add(self._shape_sig(args, None))
        return self._kv_scatter_jit(*args)

    @staticmethod
    def _shape_sig(args, kernel_mode):
        return (kernel_mode,) + tuple(
            (tuple(a.shape), str(a.dtype))
            for a in jax.tree.leaves(args) if hasattr(a, "shape"))

    def slot_compile_counts(self) -> Dict[str, int]:
        return {k: len(v) for k, v in self._compile_keys.items()}

    def input_specs(self, cell: ShapeCell) -> Dict:
        B, S = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        if cell.kind == "train":
            return dict(tokens=jax.ShapeDtypeStruct((B, S), i32),
                        targets=jax.ShapeDtypeStruct((B, S), i32))
        if cell.kind == "prefill":
            return dict(tokens=jax.ShapeDtypeStruct((B, S), i32))
        cache = jax.eval_shape(lambda: self.init_cache(B, S))
        return dict(cache=cache, tokens=jax.ShapeDtypeStruct((B,), i32))
