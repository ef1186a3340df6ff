"""Paged-allocator invariants (hypothesis state machine style) + cost-model
monotonicity + engine conservation."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.serving.cost_model import CostModel, HardwareSpec
from repro.serving.kv_cache import OutOfPages, PagedAllocator


def test_alloc_extend_free_roundtrip():
    a = PagedAllocator(n_pages=16, page_size=4)
    a.allocate("s0", 10)                      # 3 pages
    assert a.used_pages == 3
    a.extend("s0", 3)                         # 13 tokens -> 4 pages
    assert a.used_pages == 4
    a.allocate("s1", 16)                      # 4 pages
    assert a.used_pages == 8
    tbl = a.batch_block_tables(["s0", "s1"])
    assert tbl.shape == (2, 4)
    assert len(set(tbl.reshape(-1).tolist())) >= 7   # distinct physical pages
    a.free("s0")
    assert a.used_pages == 4
    a.check()


def test_out_of_pages_raises_and_preserves_state():
    a = PagedAllocator(n_pages=4, page_size=4)
    a.allocate("s0", 12)
    with pytest.raises(OutOfPages):
        a.allocate("s1", 12)
    a.check()
    assert a.can_fit(4) and not a.can_fit(8)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(["alloc", "extend", "free"]),
                          st.integers(0, 7), st.integers(1, 40)),
                min_size=1, max_size=60))
def test_allocator_never_leaks(ops):
    """Property: through arbitrary alloc/extend/free sequences, every page
    is owned exactly once or free — no leaks, no double ownership."""
    a = PagedAllocator(n_pages=32, page_size=8)
    for op, sid_i, tok in ops:
        sid = f"s{sid_i}"
        try:
            if op == "alloc" and sid not in a.seqs:
                a.allocate(sid, tok)
            elif op == "extend" and sid in a.seqs:
                a.extend(sid, tok)
            elif op == "free":
                a.free(sid)
        except OutOfPages:
            pass
        a.check()


def test_block_tables_drive_paged_kernel():
    """The allocator's tables are directly consumable by the Pallas kernel."""
    import jax.numpy as jnp
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref
    rng = np.random.default_rng(0)
    page, Hkv, D, H = 8, 2, 32, 4
    a = PagedAllocator(n_pages=12, page_size=page)
    a.allocate("x", 19)
    a.allocate("y", 7)
    tables = jnp.asarray(a.batch_block_tables(["x", "y"]))
    ctx = jnp.asarray(a.ctx_lens(["x", "y"]))
    kp = jnp.asarray(rng.normal(size=(12, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(12, Hkv, page, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, H, D)), jnp.float32)
    out = paged_attention(q, kp, vp, tables, ctx, interpret=True)
    want = paged_attention_ref(q, kp, vp, tables, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# -- cost model properties ---------------------------------------------------

CM = CostModel(get_config("llama3-8b"), HardwareSpec(chips_per_replica=2))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4096), st.integers(0, 30000))
def test_prefill_time_monotone(new, cached):
    t1 = CM.prefill_time(new, cached)
    assert CM.prefill_time(new + 16, cached) >= t1
    assert CM.prefill_time(new, cached + 512) >= t1
    assert t1 > 0


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 64), st.integers(64, 200000))
def test_decode_time_monotone_and_batch_efficient(batch, ctx):
    t = CM.decode_step_time(batch, ctx)
    assert CM.decode_step_time(batch, ctx + 4096) >= t
    # batching is sub-linear: 2x batch < 2x time (the paper's Fig 2 premise)
    assert CM.decode_step_time(batch * 2, ctx * 2) < 2 * t + 1e-9


def test_layerwise_stall_hidden_when_fetch_faster():
    step = 0.032
    fast = CM.layerwise_stall(32, 1e6, "h2d", step_time=step, n_layers=32)
    slow = CM.layerwise_stall(32, 1e9, "disk_r", step_time=step, n_layers=32)
    assert fast < slow
    assert CM.layerwise_stall(0, 1e9, "h2d", step, 32) == 0.0


# -- engine conservation -------------------------------------------------------

def test_engine_conserves_requests():
    """Every submitted request either completes or remains queued/running —
    nothing is lost through admission, preemption, or completion paths."""
    from repro.core.node_manager import NodeManager
    from repro.core.advisory import InferenceRequest
    from repro.serving.engine import NodeEngine
    cfg = get_config("llama3-8b")
    mgr = NodeManager(0, cfg, CM)
    eng = NodeEngine(0, cfg, CM, mgr, max_batch=4)
    rng = np.random.default_rng(0)
    n = 30
    for i in range(n):
        eng.submit(InferenceRequest(session_id=f"s{i}",
                                    prompt_tokens=int(rng.integers(4, 200)),
                                    max_new_tokens=int(rng.integers(1, 50))))
    now = 0.0
    for _ in range(3000):
        if not (eng.waiting or eng.running):
            break
        now += eng.step(now)
    assert len(eng.completed) == n
    for r in eng.completed:
        assert r.finished_at is not None and r.generated >= 1
        assert r.first_token_at is not None
        assert r.finished_at >= r.first_token_at >= r.arrival
