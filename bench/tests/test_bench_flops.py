"""Operation and byte counts against hand counts at small shapes; a
step's totals through the dense decoder's module."""
from types import SimpleNamespace

from bench import flops
from bench.model import load_arch

DENSE = load_arch({"reference": "dense_decoder"})

CFG = SimpleNamespace(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
                      d_head=2, d_ff=16, vocab=10)


def test_matmul_and_head():
    # per layer: q 8x8, k,v 8x4 each, o 8x8, ffn 3 x 8x16
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert flops.matmul_flops_per_token(CFG) == 2 * 2 * per_layer
    assert flops.head_flops(CFG) == 2 * 8 * 10


def test_attention_flops_are_causal():
    # 3 new tokens after 5 cached: queries see 6, 7 and 8 keys
    keys = 6 + 7 + 8
    assert flops.attn_flops(CFG, 5, 3) == 4 * 4 * 2 * keys
    # decode: one query over the whole context
    assert flops.attn_flops(CFG, 9, 1) == 4 * 4 * 2 * 10


def test_attention_bytes_per_query_block():
    # 3 tokens after 5, pages of 4: one block reads ceil(8/4)=2 pages per
    # kv head, K and V, bf16; q read and o written once
    kv = 2 * 4 * 2 * 2 * 2 * 2          # pages*page*d_head*(k,v)*2B*Hkv
    qo = 2 * 3 * 4 * 2 * 2
    assert flops.attn_bytes(CFG, 5, 3, 4, 2) == kv + qo
    # an int8 shadow tile rides with every page: 3 bytes per element
    assert flops.attn_bytes(CFG, 5, 3, 4, 3) == kv * 3 // 2 + qo


def test_long_chunk_reads_pages_up_to_each_block_horizon():
    # 256 new tokens at offset 0, pages of 16: block 1 reads 8 pages,
    # block 2 reads 16
    b = flops.attn_bytes(CFG, 0, 256, 16, 2)
    pages = 8 + 16
    assert b == pages * 16 * 2 * 2 * 2 * 2 + 2 * 256 * 4 * 2 * 2


def test_step_totals():
    lanes = [(5, 3, True), (9, 1, True), (0, 4, False)]
    want = (8 * flops.matmul_flops_per_token(CFG)
            + 2 * sum(flops.attn_flops(CFG, o, n) for o, n, _ in lanes)
            + 2 * flops.head_flops(CFG))
    assert DENSE.step_flops(CFG, lanes) == want
    f, b = DENSE.step_attn(CFG, lanes, 4, 2)
    assert f == 2 * sum(flops.attn_flops(CFG, o, n) for o, n, _ in lanes)
    assert b == 2 * sum(flops.attn_bytes(CFG, o, n, 4, 2)
                        for o, n, _ in lanes)
