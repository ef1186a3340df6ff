"""Per-architecture sharding plans (GSPMD PartitionSpecs).

Strategy (DESIGN.md §4):
  * weights: Megatron column/row TP over ``model``; experts EP over ``model``
    when E % tp == 0 else TP-inside-expert; embeddings vocab-sharded.
  * batch dims over ``data`` (x ``pod``): the replica axis.
  * KV caches: batch->data when divisible; kv-heads->model when divisible,
    else sequence->model (GSPMD then derives split-K "flash decoding" with a
    softmax combine — the TPU-native plan for GQA archs whose kv_heads < 16).
  * optimizer state: ZeRO-1 — param spec + an extra ``data`` axis on the
    largest still-unsharded dim.

Every choice is divisibility-checked with replication fallback; the dry-run
is the arbiter.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell

# parameter-name classes
_COL = {"wq", "wk", "wv", "w1", "w3", "wz", "wxbc", "wdt", "w_up", "w_ifzo",
        "w_f1", "w_f3", "xq", "xk", "xv", "frame_proj", "patch_proj", "w_out"}
_ROW = {"wo", "w2", "wout", "w_down", "w_f2", "xo"}
_VOCAB = {"emb", "lm_head"}
_REPL = {"ln", "ln1", "ln2", "lnx", "ln_f", "ln_enc", "gn", "norm", "qn", "kn",
         "a_log", "dt_bias", "d_skip", "b_if", "b_ifzo", "len", "wif"}


class ShardingPlan:
    def __init__(self, cfg: ModelConfig, mesh):
        self.cfg = cfg
        self.mesh = mesh
        ax = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.tp = ax.get("model", 1)
        self.dp = ax.get("data", 1)
        self.pod = ax.get("pod", 1)
        self.dp_axes: Tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in ax)
        self.dp_total = self.dp * self.pod

    # -- helpers ---------------------------------------------------------------

    def _ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _axis_size(self, axis) -> int:
        if isinstance(axis, tuple):
            size = 1 if axis else 0
            for a in axis:
                size *= self._axis_size(a)
            return size
        return {"model": self.tp, "data": self.dp, "pod": self.pod}.get(axis, 0)

    def _div(self, n: int, axis) -> bool:
        # an axis absent from the mesh (size 0 here) or of size 1 has
        # nothing to shard over: report non-divisible so the spec falls
        # back to replication instead of naming an axis the NamedSharding
        # would reject (serving meshes are ("model",)-only)
        size = self._axis_size(axis)
        if size <= 1:
            return False
        return n % size == 0 and n >= size

    # -- parameters --------------------------------------------------------------

    def param_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        nd = len(shape)
        mp = "model"
        if name in _REPL or nd == 0 or nd == 1:
            return P()
        if name in _VOCAB:
            if self._div(shape[0], mp):
                return P(mp, None)
            if self._div(shape[1], mp):
                return P(None, mp)
            return P()
        if name == "router":                       # (L, D, E)
            return P(None, None, mp) if self._div(shape[-1], mp) else P()
        if name in ("we1", "we3"):                 # (L, E, D, F)
            if self._div(shape[1], mp):
                return P(None, mp, None, None)     # EP
            if self._div(shape[3], mp):
                return P(None, None, None, mp)     # TP inside expert
            return P()
        if name == "we2":                          # (L, E, F, D)
            if self._div(shape[1], mp):
                return P(None, mp, None, None)
            if self._div(shape[2], mp):
                return P(None, None, mp, None)
            return P()
        if name == "conv_w":                       # (L, dim, k)
            return P(None, mp, None) if self._div(shape[1], mp) else P()
        if name == "r_ifzo":                       # (L, NH, ph, 4ph)
            return P(None, None, None, mp) if self._div(shape[-1], mp) else P()
        if name in ("wq", "wk", "wv") and nd == 4:  # xlstm blockdiag (L,NH,dv,dqk)
            return P(None, None, None, mp) if self._div(shape[-1], mp) else P()
        if name in _COL:
            if self._div(shape[-1], mp):
                return P(*([None] * (nd - 1) + [mp]))
            return P()
        if name in _ROW:
            if self._div(shape[-2], mp):
                return P(*([None] * (nd - 2) + [mp, None]))
            return P()
        return P()

    def params_specs(self, abstract_params) -> Dict:
        def leaf(path, x):
            name = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
            return self.param_spec(name, x.shape)
        return jax.tree_util.tree_map_with_path(leaf, abstract_params)

    def params_shardings(self, abstract_params):
        return jax.tree.map(self._ns, self.params_specs(abstract_params))

    # -- optimizer state (ZeRO-1) ---------------------------------------------------

    def opt_spec_from_param(self, spec: P, shape: Tuple[int, ...]) -> P:
        parts = list(spec) + [None] * (len(shape) - len(spec))
        # add `data` on the largest unsharded, divisible dim
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if parts[i] is None and self._div(shape[i], "data"):
                parts[i] = "data"
                break
        return P(*parts)

    # -- batch -----------------------------------------------------------------------

    def data_spec(self, shape: Tuple[int, ...]) -> P:
        if len(shape) == 0:
            return P()
        if self._div(shape[0], self.dp_axes):
            return P(*((self.dp_axes,) + (None,) * (len(shape) - 1)))
        if self._div(shape[0], "data"):
            return P(*(("data",) + (None,) * (len(shape) - 1)))
        return P()

    def batch_specs(self, batch) -> Dict:
        return jax.tree.map(lambda x: self.data_spec(x.shape), batch)

    # -- caches ------------------------------------------------------------------------

    def cache_spec(self, key: str, shape: Tuple[int, ...]) -> P:
        """Session-state sharding. Leading dim is the stacked layer dim."""
        if key == "len" or len(shape) <= 1:
            return P()
        parts: list = [None] * len(shape)
        # dim roles per key
        kv_like = key in ("k", "v", "xk", "xv", "attn_k", "attn_v")
        if kv_like:                                   # (L, B, S, H, Dh)
            Ldim, Bdim, Sdim, Hdim, Ddim = range(5)
            if self._div(shape[Bdim], self.dp_axes):
                parts[Bdim] = self.dp_axes
            elif self._div(shape[Sdim], "data"):
                parts[Sdim] = "data"
            if self._div(shape[Hdim], "model"):
                parts[Hdim] = "model"
            elif parts[Sdim] is None and self._div(shape[Sdim], "model"):
                parts[Sdim] = "model"               # split-K decode
            elif self._div(shape[Ddim], "model"):
                parts[Ddim] = "model"
            return P(*parts)
        # generic state tensors (ssm, conv, m_C, m_n, s_*, ...):
        # batch dim is dim 1; try dp there (or on the largest later dim),
        # then mp on the largest remaining dim.
        if self._div(shape[1], self.dp_axes):
            parts[1] = self.dp_axes
        order = sorted(range(2, len(shape)), key=lambda i: -shape[i])
        if parts[1] is None:
            for i in order:
                if self._div(shape[i], "data"):
                    parts[i] = "data"
                    break
        for i in order:
            if parts[i] is None and self._div(shape[i], "model"):
                parts[i] = "model"
                break
        return P(*parts)

    def pool_spec(self, shape: Tuple[int, ...]) -> P:
        """Sharding of the serving backend's STACKED head-major page pool
        ``(L, P+1, Hkv, page, D)``.  Same tensor-parallel ladder as
        `cache_spec`'s kv-like branch: kv-heads -> ``model`` when divisible
        (the only split the per-shard Pallas kernel serves), else the
        split-K sequence fallback on the page-slot dim (GSPMD then derives
        a flash-decoding-style softmax combine over the jnp oracle, the GQA
        plan whose kv_heads < tp), else the head-feature dim, else
        replicate.  The layer and page-index dims are NEVER sharded: a
        block-table entry must address the same page on every shard (tier
        transfers and CoW forks are per-page), and the trash page (index
        P) must exist on every shard."""
        Ldim, Pdim, Hdim, Sdim, Ddim = range(5)
        parts: list = [None] * len(shape)
        if self._div(shape[Hdim], "model"):
            parts[Hdim] = "model"
        elif self._div(shape[Sdim], "model"):
            parts[Sdim] = "model"
        elif self._div(shape[Ddim], "model"):
            parts[Ddim] = "model"
        return P(*parts)

    def pool_sharding(self, shape: Tuple[int, ...]) -> NamedSharding:
        return self._ns(self.pool_spec(shape))

    def cache_specs(self, abstract_cache) -> Dict:
        def leaf(path, x):
            name = str(path[-1].key) if hasattr(path[-1], "key") else str(path[-1])
            return self.cache_spec(name, x.shape)
        return jax.tree_util.tree_map_with_path(leaf, abstract_cache)

    # -- outputs ------------------------------------------------------------------------

    def logits_spec(self, shape: Tuple[int, ...]) -> P:
        parts: list = [None] * len(shape)
        if self._div(shape[0], self.dp_axes):
            parts[0] = self.dp_axes
        if self._div(shape[-1], "model"):
            parts[-1] = "model"
        return P(*parts)
