"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all against the pure-jnp oracles in kernels/ref.py (interpret=True executes
the Pallas kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention, \
    paged_chunk_attention

TOL = dict(rtol=2e-2, atol=2e-2)      # bf16-friendly
TOL32 = dict(rtol=2e-4, atol=2e-4)


def _mk_paged(rng, B, H, Hkv, D, page, maxp, dtype):
    P = maxp * B + 2
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(P, Hkv, page, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, page, D)), dtype)
    tables = jnp.asarray(
        rng.permutation(P)[:B * maxp].reshape(B, maxp), jnp.int32)
    ctx = jnp.asarray(rng.integers(1, maxp * page + 1, (B,)), jnp.int32)
    return q, kp, vp, tables, ctx


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,page,maxp", [
    (2, 4, 4, 32, 8, 3),       # MHA
    (3, 8, 2, 64, 16, 4),      # GQA 4:1
    (1, 8, 1, 128, 32, 2),     # MQA
    (2, 36, 36, 64, 8, 2),     # minicpm-like head count
])
def test_paged_attention_sweep(B, H, Hkv, D, page, maxp, dtype):
    rng = np.random.default_rng(hash((B, H, D)) % 2**32)
    args = _mk_paged(rng, B, H, Hkv, D, page, maxp, dtype)
    out = paged_attention(*args, interpret=True)
    want = ref.paged_attention_ref(*args)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


@settings(max_examples=12, deadline=None)
@given(B=st.integers(1, 3), G=st.sampled_from([1, 2, 4]),
       Hkv=st.sampled_from([1, 2, 4]), page=st.sampled_from([8, 16]),
       maxp=st.integers(1, 4), seed=st.integers(0, 10**6))
def test_paged_attention_property(B, G, Hkv, page, maxp, seed):
    """Property: kernel == oracle for arbitrary GQA geometry + ctx lens."""
    rng = np.random.default_rng(seed)
    args = _mk_paged(rng, B, Hkv * G, Hkv, 32, page, maxp, jnp.float32)
    out = paged_attention(*args, interpret=True)
    want = ref.paged_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL32)


def test_paged_attention_page_permutation_invariance():
    """Property (paper invariant): physical page placement must not matter —
    permuting the pool + remapping tables gives identical output.  This is
    what makes KV migration transparent to attention."""
    rng = np.random.default_rng(7)
    q, kp, vp, tables, ctx = _mk_paged(rng, 2, 8, 4, 32, 8, 3, jnp.float32)
    out1 = paged_attention(q, kp, vp, tables, ctx, interpret=True)
    P = kp.shape[0]
    perm = jnp.asarray(np.random.default_rng(8).permutation(P), jnp.int32)
    inv = jnp.argsort(perm)
    out2 = paged_attention(q, kp[perm], vp[perm], inv[tables], ctx,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


def _mk_chunk(rng, B, Sq, H, Hkv, D, page, maxp, dtype, decode=False,
              L=1):
    """Stacked (L, P, Hkv, page, D) pools; the chunk kernels read one
    layer of them."""
    P = maxp * B + 2
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(L, P, Hkv, page, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(L, P, Hkv, page, D)), dtype)
    tables = jnp.asarray(
        rng.permutation(P)[:B * maxp].reshape(B, maxp), jnp.int32)
    if decode:                                  # q_len 1: qoff = ctx - 1
        ctx = rng.integers(1, maxp * page + 1, (B,))
        qoff = ctx - 1
    else:                                       # mixed chunk lengths
        qlen = rng.integers(1, Sq + 1, (B,))
        qoff = rng.integers(0, maxp * page - Sq + 1, (B,))
        ctx = qoff + qlen
    return (q, kp, vp, tables, jnp.asarray(qoff, jnp.int32),
            jnp.asarray(ctx, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,H,Hkv,D,page,maxp", [
    (2, 8, 4, 4, 32, 8, 4),        # MHA, mixed chunks
    (3, 16, 8, 2, 64, 16, 4),      # GQA 4:1
    (1, 8, 8, 1, 128, 32, 2),      # MQA
])
def test_paged_chunk_attention_sweep(B, Sq, H, Hkv, D, page, maxp, dtype):
    """Unified kernel vs oracle on mixed per-lane (q_len, ctx) geometry;
    only each lane's valid query rows are compared (padded rows are
    garbage by contract)."""
    rng = np.random.default_rng(hash((B, Sq, H)) % 2**32)
    args = _mk_chunk(rng, B, Sq, H, Hkv, D, page, maxp, dtype)
    out = paged_chunk_attention(*args, layer=0, bq=4, interpret=True)
    want = ref.paged_chunk_attention_ref(*args, layer=0)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    qoff, ctx = np.asarray(args[4]), np.asarray(args[5])
    for b in range(B):
        qlen = int(ctx[b] - qoff[b])
        np.testing.assert_allclose(np.asarray(out[b, :qlen], np.float32),
                                   np.asarray(want[b, :qlen], np.float32),
                                   **tol)


def test_paged_chunk_attention_decode_is_special_case():
    """A batch of q_len = 1 lanes must agree with the dedicated decode
    kernel's oracle — decode is the one-token chunk, not a separate path."""
    rng = np.random.default_rng(23)
    q, kp, vp, tables, qoff, ctx = _mk_chunk(
        rng, 3, 1, 8, 4, 32, 8, 4, jnp.float32, decode=True)
    out = paged_chunk_attention(q, kp, vp, tables, qoff, ctx, layer=0,
                                bq=1, interpret=True)
    want = ref.paged_attention_ref(q[:, 0], kp[0], vp[0], tables, ctx)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want),
                               **TOL32)


def test_paged_chunk_attention_padded_lane_is_inert():
    """ctx_len = 0 lanes must finish as zeros without poisoning the batch,
    and their presence must not change live lanes' outputs."""
    rng = np.random.default_rng(29)
    q, kp, vp, tables, qoff, ctx = _mk_chunk(
        rng, 3, 8, 4, 2, 32, 8, 4, jnp.float32)
    full = paged_chunk_attention(q, kp, vp, tables, qoff, ctx, layer=0,
                                 bq=4, interpret=True)
    ctx_pad = ctx.at[1].set(0)
    out = paged_chunk_attention(q, kp, vp, tables, qoff, ctx_pad, layer=0,
                                bq=4, interpret=True)
    np.testing.assert_allclose(np.asarray(out[1]), 0.0)
    for b in (0, 2):
        qlen = int(ctx[b] - qoff[b])
        np.testing.assert_allclose(np.asarray(out[b, :qlen]),
                                   np.asarray(full[b, :qlen]),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,D,qo", [
    (2, 64, 64, 4, 4, 32, 0),        # plain causal
    (1, 32, 96, 8, 2, 64, 64),       # continuation: 64 cached + 32 new
    (2, 16, 48, 4, 1, 32, 32),       # MQA continuation
])
def test_flash_prefill_sweep(B, Sq, Skv, H, Hkv, D, qo, dtype):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), dtype)
    out = flash_prefill(q, k, v, q_offset=qo, bq=16, bk=16, interpret=True)
    want = ref.flash_prefill_ref(q, k, v, qo)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


@settings(max_examples=10, deadline=None)
@given(bq=st.sampled_from([8, 16, 32]), bk=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 10**6))
def test_flash_prefill_block_shape_invariance(bq, bk, seed):
    """Property: output must not depend on BlockSpec tiling."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 2, 32)).reshape(1, 32, 8, 32)[:, :, :4],
                    jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.float32)
    out = flash_prefill(q, k, v, q_offset=32, bq=bq, bk=bk, interpret=True)
    want = ref.flash_prefill_ref(q, k, v, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL32)


def test_flash_prefill_matches_two_stage():
    """SYMPHONY's continuation invariant at the kernel level: prefill of
    [prefix + new] == prefill(prefix) KV cached, then prefill(new, offset)."""
    rng = np.random.default_rng(11)
    B, S1, S2, H, Hkv, D = 1, 32, 32, 4, 2, 32
    x_q = jnp.asarray(rng.normal(size=(B, S1 + S2, H, D)), jnp.float32)
    x_k = jnp.asarray(rng.normal(size=(B, S1 + S2, Hkv, D)), jnp.float32)
    x_v = jnp.asarray(rng.normal(size=(B, S1 + S2, Hkv, D)), jnp.float32)
    full = flash_prefill(x_q, x_k, x_v, q_offset=0, bq=16, bk=16,
                         interpret=True)
    cont = flash_prefill(x_q[:, S1:], x_k, x_v, q_offset=S1, bq=16, bk=16,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(full[:, S1:]), np.asarray(cont),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 64, 2, 16, 8, 16),
    (1, 128, 4, 32, 16, 64),
    (3, 96, 1, 8, 4, 32),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    from repro.kernels.ssd_scan import ssd_scan
    rng = np.random.default_rng(hash((B, S, H)) % 2**32)
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), dtype)
    dA = jnp.asarray(-np.abs(rng.normal(scale=0.1, size=(B, S, H))),
                     jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, H, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=(B, S, H, N)), dtype)
    y = ssd_scan(x, dA, Bm, Cm, chunk=chunk, interpret=True)
    yr, _ = ref.ssd_scan_ref(x, dA, Bm, Cm)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)


def test_model_ssd_matches_sequential_oracle():
    """The Zamba2 model's chunked jnp SSD path == the token-by-token
    recurrence (cross-validates both against each other)."""
    from repro.configs import get_config
    from repro.models.registry import get_model
    cfg = get_config("zamba2-2.7b").reduced()
    model = get_model(cfg)
    rng = np.random.default_rng(5)
    B, S = 2, 64
    H, P, N = model.nh, cfg.ssm.head_dim, cfg.ssm.d_state
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(scale=0.3, size=(B, S, H))),
                     jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.float32)
    a_log = jnp.asarray(np.log(np.arange(1, H + 1)), jnp.float32)
    y, state = model._ssd_scan(xh, dt, Bm, Cm, a_log)
    A = -jnp.exp(a_log)
    dA = dt * A
    xdt = xh * dt[..., None]
    Bh = jnp.repeat(Bm, H, axis=2)
    Ch = jnp.repeat(Cm, H, axis=2)
    yr, state_r = ref.ssd_scan_ref(xdt, dA, Bh, Ch)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(state),
                               np.asarray(state_r), rtol=5e-4, atol=5e-4)
