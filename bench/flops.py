"""Operations and bytes a step needs, from its shapes alone.

A lane of a serving step processes ``q_len`` new tokens at positions
``[q_off, q_off + q_len)``: its KV is written there and each of its
queries attends causally to every earlier position.  These functions
count the work the model requires for that, never what the program pads
or recomputes, so a share of a peak computed from them cannot pass 100%
unless the time is counted short.
"""
from __future__ import annotations

Q_BLOCK = 128            # query rows per attention kernel block


def matmul_flops_per_token(cfg) -> int:
    """Projections and feed-forward of every held layer, per token."""
    q = cfg.n_heads * cfg.d_head
    kv = cfg.n_kv_heads * cfg.d_head
    per_layer = cfg.d_model * (q + 2 * kv) + q * cfg.d_model \
        + 3 * cfg.d_model * cfg.d_ff
    return 2 * per_layer * cfg.n_layers


def head_flops(cfg) -> int:
    """The output head at one sampled position."""
    return 2 * cfg.d_model * cfg.vocab


def attn_flops(cfg, q_off: int, q_len: int) -> int:
    """Scores and weighted values of one lane in ONE layer: query j sees
    ``q_off + j + 1`` keys, two matmuls of 2 * d_head FLOPs per head."""
    keys = q_len * q_off + q_len * (q_len + 1) // 2
    return 4 * cfg.n_heads * cfg.d_head * keys


def attn_bytes(cfg, q_off: int, q_len: int, page: int,
               kv_bytes_per_elem: float) -> int:
    """Bytes one lane's attention reads and writes in ONE layer: per kv
    head, each block of `Q_BLOCK` real query rows reads the pages up to
    its causal horizon (what the kernel's clamped index maps fetch); the
    queries are read and the output written once.  ``kv_bytes_per_elem``
    is what one K or V element costs to read: the pool's itemsize, plus 1
    where the int8 shadow tile of each page is fetched with it."""
    ctx = q_off + q_len
    npg = -(-ctx // page)
    pages = 0
    for lo in range(0, q_len, Q_BLOCK):
        horizon = -(-(q_off + min(lo + Q_BLOCK, q_len)) // page)
        pages += min(npg, horizon)
    kv = pages * page * cfg.d_head * 2 * kv_bytes_per_elem * cfg.n_kv_heads
    qo = 2 * q_len * cfg.n_heads * cfg.d_head * 2
    return int(kv + qo)


def step_flops(cfg, lanes) -> int:
    """Model FLOPs of one engine step.  ``lanes``: (q_off, q_len, emits)
    triples; ``emits`` lanes pay the head at their last position."""
    mm = matmul_flops_per_token(cfg)
    total = 0
    for q_off, q_len, emits in lanes:
        total += q_len * mm + cfg.n_layers * attn_flops(cfg, q_off, q_len)
        if emits:
            total += head_flops(cfg)
    return total


def step_attn(cfg, lanes, page: int, kv_bytes_per_elem: float):
    """(FLOPs, bytes) of the attention kernel over all held layers."""
    f = sum(attn_flops(cfg, o, n) for o, n, _ in lanes)
    b = sum(attn_bytes(cfg, o, n, page, kv_bytes_per_elem)
            for o, n, _ in lanes)
    return cfg.n_layers * f, cfg.n_layers * b
