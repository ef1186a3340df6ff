"""The serving step's stacked KV pools, read and written in place.

The fused step carries the stacked ``(L, P+1, Hkv, page, D)`` pools through
its layer scan: layer l's KV is written at ``[l, page, head, off]`` and the
attention kernel reads layer l of the stacked pool through its index map.
Two things must hold:

* the layer coordinate selects exactly one layer — the kernel (interpret
  mode and the jnp oracle, fp and int8) over layer l of a stacked pool
  whose layers all differ equals the kernel over that layer alone;
* the in-place write touches exactly its slots — the write kernel, which
  read-modify-writes whole pages found through the block tables, writes
  the rows the per-row scatter oracle writes; after one fused step the
  pools differ from their inputs only where ``slot_pages``/``slot_offs``
  point, in every layer, and hold the K/V the dense reference computes
  there.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.quant import quantize_int8
from repro.models.registry import get_model


def _stacked_case(seed: int, L=3, B=3, Sq=8, H=8, Hkv=2, D=32, page=8,
                  maxp=4):
    rng = np.random.default_rng(seed)
    P = maxp * B + 2
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(L, P, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, P, Hkv, page, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(P)[:B * maxp].reshape(B, maxp), jnp.int32)
    qlen = rng.integers(1, Sq + 1, (B,))
    qoff = rng.integers(0, maxp * page - Sq + 1, (B,))
    kq, ks = quantize_int8(kp.reshape(L * P, Hkv, page, D), axis=(1, 2, 3))
    vq, vs = quantize_int8(vp.reshape(L * P, Hkv, page, D), axis=(1, 2, 3))
    flags = jnp.asarray(rng.integers(0, 2, (L, P)), jnp.int32)
    quant = (kq.reshape(kp.shape), vq.reshape(vp.shape),
             ks.reshape(L, P), vs.reshape(L, P), flags)
    return (q, kp, vp, tables, jnp.asarray(qoff, jnp.int32),
            jnp.asarray(qoff + qlen, jnp.int32)), quant


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_layer_coordinate_reads_that_layer(mode, quant):
    (q, kp, vp, tab, qo, ctx), (kq, vq, ks, vs, pq) = _stacked_case(3)
    outs = []
    for layer in range(kp.shape[0]):
        qt = (kq, vq, ks[layer], vs[layer], pq[layer]) if quant else None
        one = (kq[layer][None], vq[layer][None], ks[layer], vs[layer],
               pq[layer]) if quant else None
        got = ops.paged_chunk_attention(q, kp, vp, tab, qo, ctx, mode=mode,
                                        quant=qt, layer=layer)
        want = ops.paged_chunk_attention(q, kp[layer][None],
                                         vp[layer][None], tab, qo, ctx,
                                         mode=mode, quant=one, layer=0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        outs.append(np.asarray(got))
    # the layers hold different KV, so each layer's output is its own
    assert not np.allclose(outs[0], outs[1])
    assert not np.allclose(outs[1], outs[2])


@pytest.mark.parametrize("Sq", [1, 8, 24])
def test_kv_write_kernel_matches_scatter(Sq):
    """Chunks that start anywhere in a page, cross pages and leave padded
    rows, a padded lane (ctx 0, a table of page 0 as the backend builds
    it): the kernel writes what the scatter writes, on every live page."""
    rng = np.random.default_rng(Sq)
    L, P, Hkv, page, D, B, T, layer = 3, 40, 2, 8, 32, 4, 6, 1
    kp = jnp.asarray(rng.normal(size=(L, P + 1, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, P + 1, Hkv, page, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sq, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sq, Hkv, D)), jnp.float32)
    tables = rng.permutation(P)[:B * T].reshape(B, T).astype(np.int32)
    qoff = rng.integers(0, T * page - Sq + 1, B).astype(np.int32)
    n = rng.integers(1, Sq + 1, B)
    tables[B - 1], qoff[B - 1], n[B - 1] = 0, 0, 0          # padded lane
    ctx = (qoff + n).astype(np.int32)
    sp = np.full((B, Sq), P, np.int32)                      # trash slot
    so = np.zeros((B, Sq), np.int32)
    for b in range(B):
        pos = qoff[b] + np.arange(n[b])
        sp[b, :n[b]] = tables[b, pos // page]
        so[b, :n[b]] = pos % page
    args = (kp, vp, k, v, jnp.asarray(tables), jnp.asarray(qoff),
            jnp.asarray(ctx), jnp.asarray(sp), jnp.asarray(so))
    got = ops.paged_kv_write(*args, mode="interpret", layer=layer)
    want = ops.paged_kv_write(*args, mode="ref", layer=layer)
    for g, w, old in zip(got, want, (kp, vp)):
        g, w = np.asarray(g)[:, :P], np.asarray(w)[:, :P]
        np.testing.assert_array_equal(g, w)
        assert not np.array_equal(g, np.asarray(old)[:, :P])


def _model():
    cfg = get_config("llama3-8b").reduced(dtype="float32", n_kv_heads=2)
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_fused_step_writes_only_its_slots(mode):
    """Two fresh lanes, 13 and 6 prompt tokens, in a 16-token bucket: each
    layer gets its own pages, the 10 padded slots aim at the trash page."""
    cfg, model, params = _model()
    L, Hkv, D, page, P, B, Sq = (cfg.n_layers, cfg.n_kv_heads, cfg.d_head,
                                 8, 12, 2, 16)
    rng = np.random.default_rng(11)
    lens = [13, 6]
    ids = np.zeros((B, Sq), np.int32)
    tables = np.zeros((L, B, 2), np.int32)
    sp = np.full((L, B, Sq), P, np.int32)        # trash slot
    so = np.zeros((L, B, Sq), np.int32)
    for b, n in enumerate(lens):
        ids[b, :n] = rng.integers(0, cfg.vocab, n)
    for layer in range(L):
        pages = rng.permutation(P)[:3]
        own = [pages[:2], pages[2:]]
        for b, n in enumerate(lens):
            w = len(own[b])
            tables[layer, b, :w] = own[b]
            tables[layer, b, w:] = own[b][-1]
            pos = np.arange(n)
            sp[layer, b, :n] = own[b][pos // page]
            so[layer, b, :n] = pos % page
    shape = (L, P + 1, Hkv, page, D)
    k_in = rng.normal(size=shape).astype(np.float32)
    v_in = rng.normal(size=shape).astype(np.float32)
    zeros = jnp.zeros((B,), jnp.int32)
    ctx = jnp.asarray(lens, jnp.int32)
    _, _, k_out, v_out = model.step_paged(
        params, ids, jnp.asarray(k_in), jnp.asarray(v_in), tables, zeros,
        ctx, ctx - 1, sp, so, kernel_mode=mode)
    k_out, v_out = np.asarray(k_out), np.asarray(v_out)

    touched = np.zeros(shape[:4], bool)
    touched[:, P] = True                          # padded slots' trash page
    for layer in range(L):
        for b, n in enumerate(lens):
            touched[layer, sp[layer, b, :n], :, so[layer, b, :n]] = True
    np.testing.assert_array_equal(k_out[~touched], k_in[~touched])
    np.testing.assert_array_equal(v_out[~touched], v_in[~touched])

    prefill = jax.jit(model.prefill)
    for b, n in enumerate(lens):
        _, cache = prefill(params, jnp.asarray(ids[b:b + 1, :n]))
        # cache: (L, 1, Hkv, n, D); the pools hold (L, n, Hkv, D) rows
        want_k = np.asarray(cache["k"][:, 0]).transpose(0, 2, 1, 3)
        want_v = np.asarray(cache["v"][:, 0]).transpose(0, 2, 1, 3)
        for layer in range(L):
            at = (layer, sp[layer, b, :n], slice(None), so[layer, b, :n])
            np.testing.assert_allclose(k_out[at], want_k[layer],
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(v_out[at], want_v[layer],
                                       rtol=1e-4, atol=1e-4)
