"""RealBackend numerical-parity harness (the tentpole's correctness gate).

The same multi-turn greedy conversation is served two ways:

* dense reference — full-recompute `model.prefill`/`model.decode_step`
  (pure-jnp attention, the repo's correctness oracle lineage: these match
  kernels/ref.py by tests/test_kernels.py);
* RealBackend through the NodeEngine — paged page pools, flash_prefill
  continuation over reused KV, paged_attention batched decode, and real
  swap/evict/promote copies between tiers.

Token ids must match exactly and per-token logits within fp32 tolerance,
across ≥3 turns including a preemption swap-out/swap-in round trip — so any
disagreement between the allocator, the tiered store, and the kernels shows
up as a failed assert rather than silent corruption.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.advisory import InferenceRequest
from repro.core.node_manager import NodeManager
from repro.models.registry import get_model
from repro.serving.backend import RealBackend
from repro.serving.cost_model import CostModel, HardwareSpec
from repro.serving.engine import NodeEngine

GEN = 6
TOL = dict(rtol=2e-3, atol=2e-3)


def _cfg(kind: str):
    # llama3-8b.reduced() is 4 query heads; kv head count sets the geometry
    n_kv = dict(mha=4, gqa=2)[kind]
    return get_config("llama3-8b").reduced(dtype="float32", n_kv_heads=n_kv)


def _setup(kind: str, seed: int = 0, **backend_kw):
    cfg = _cfg(kind)
    model = get_model(cfg)
    params = model.init(jax.random.key(seed))
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    be = RealBackend(cfg, model, params, mgr=mgr,
                     **{**dict(n_pages=32, page_size=8), **backend_kw})
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=4, backend=be)
    return cfg, model, params, mgr, be, eng


def _turns(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, cfg.vocab, n))) for n in lens]


def _dense_reference(cfg, model, params, turns, gen=GEN):
    """Greedy multi-turn serve by full recompute each turn (the quickstart
    equivalence: recompute == continuation for the same weights)."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    history, out, logit_trail = [], [], []
    for t in turns:
        history = history + list(t)
        logits, cache = prefill(params, jnp.asarray([history], jnp.int32))
        cache = model.grow_cache(cache, gen)
        outs = []
        for _ in range(gen):
            lg = logits[0, :cfg.vocab]
            logit_trail.append(np.asarray(lg))
            nxt = jnp.argmax(lg)[None].astype(jnp.int32)
            outs.append(int(nxt[0]))
            logits, cache = decode(params, cache, nxt)
        out.append(outs)
        history = history + outs
    return out, logit_trail


def _serve(eng, be, turns, gen=GEN, preempt_turn=None, sid="s0"):
    """Drive the engine turn by turn; optionally preempt mid-decode."""
    outs, cached, now = [], 0, 0.0
    for i, t in enumerate(turns):
        req = InferenceRequest(session_id=sid, prompt_tokens=len(t),
                               max_new_tokens=gen, prompt_ids=list(t),
                               cached_tokens=cached)
        eng.submit(req)
        preempted = False
        while eng.waiting or eng.running:
            now += eng.step(now)
            if (i == preempt_turn and not preempted and eng.running
                    and req.generated >= gen // 2):
                eng.preempt_one(now)          # swap-out -> resume round trip
                preempted = True
        outs.append(req.output_ids)
        cached = be.session_tokens(sid)
    return outs


@pytest.mark.parametrize("kind", ["mha", "gqa"])
def test_multiturn_parity_with_preemption(kind):
    cfg, model, params, mgr, be, eng = _setup(kind)
    turns = _turns(cfg, (11, 7, 9))
    want, want_logits = _dense_reference(cfg, model, params, turns)
    got = _serve(eng, be, turns, preempt_turn=1)
    assert got == want, f"token divergence ({kind}): {got} vs {want}"
    assert be.stats["swaps_out"] >= 1 and be.stats["swaps_in"] >= 1
    # per-token logits within fp32 tolerance, across the swap round trip
    trace = [lg for _sid, lg in be.logit_trace]
    assert len(trace) == len(want_logits)
    for got_lg, want_lg in zip(trace, want_logits):
        np.testing.assert_allclose(got_lg, want_lg, **TOL)


@pytest.mark.parametrize("kind", ["mha", "gqa"])
def test_served_logits_match_float32_reference(kind):
    """The plain float32 reference (models/reference.py), which the chip
    smoke holds the served bf16 path to at published widths, agrees here
    with the served f32 path at every generated position of a two-turn
    conversation: one full-sequence forward pass, no cache."""
    from repro.models.reference import dense_logits
    cfg, model, params, mgr, be, eng = _setup(kind)
    turns = _turns(cfg, (11, 7), seed=2)
    (o1, o2) = _serve(eng, be, turns)
    seq = turns[0] + o1 + turns[1] + o2[:-1]
    at = [len(turns[0]) - 1 + i for i in range(GEN)] + \
        [len(turns[0]) + GEN + len(turns[1]) - 1 + i for i in range(GEN)]
    want = dense_logits(cfg, params, seq, at)
    got = np.stack([lg for _sid, lg in be.logit_trace])
    np.testing.assert_allclose(got, want, **TOL)


def test_cooperative_evict_then_promote_preserves_kv():
    """Layer-granular eviction (node-manager cooperative purge) followed by
    priority promotion must physically round-trip page contents."""
    cfg, model, params, mgr, be, eng = _setup("gqa")
    turns = _turns(cfg, (10, 8), seed=3)
    want, _ = _dense_reference(cfg, model, params, turns)
    got = [_serve(eng, be, turns[:1])[0]]
    # idle between turns: purge everything the store will give up
    mgr.on_memory_pressure(be.hbm_kv_budget() * 10, now=1.0)
    assert be.stats["layer_evictions"] == cfg.n_layers
    assert all("s0" not in a.seqs for a in be.alloc)      # pages really freed
    # advisory-style promotion copies the layers back, lowest first
    mgr.promote("s0", now=2.0)
    assert be.stats["layer_promotions"] == cfg.n_layers
    assert all("s0" in a.seqs for a in be.alloc)
    cached = be.session_tokens("s0")
    req = InferenceRequest(session_id="s0", prompt_tokens=len(turns[1]),
                           max_new_tokens=GEN, prompt_ids=list(turns[1]),
                           cached_tokens=cached)
    eng.submit(req)
    now = 3.0
    while eng.waiting or eng.running:
        now += eng.step(now)
    got.append(req.output_ids)
    assert got == want


def test_disk_spool_recovers_lost_host_tier(tmp_path):
    """Persistent-copy invariant, for real: after a disk write-through the
    host tier can be lost entirely and the session still resumes bit-true.
    Persist and swap-out only LAUNCH their copies now — losing the host
    tier "for real" requires draining the in-flight transfers first (an
    undrained loss is the crash path, covered by test_transfer_engine)."""
    cfg, model, params, mgr, be, eng = _setup("gqa", spool_dir=str(tmp_path))
    turns = _turns(cfg, (12, 6), seed=5)
    want, _ = _dense_reference(cfg, model, params, turns)
    got = [_serve(eng, be, turns[:1])[0]]
    assert be.persist("s0")
    assert not (tmp_path / "s0.npz").exists()   # launched, not yet landed
    be.drain_transfers()
    assert (tmp_path / "s0.npz").exists()
    be.swap_out("s0", be.session_tokens("s0"))
    be.drain_transfers()                      # host copies land, pages free
    be.host.clear()                           # simulate losing the fast tiers
    got.append(_serve(eng, be, turns[1:])[0])
    assert got == want


def test_batched_decode_two_sessions():
    """Batched paged_attention decode over sequences of different lengths
    matches each session's independent dense reference."""
    cfg, model, params, mgr, be, eng = _setup("mha", seed=1)
    prompts = {"a": _turns(cfg, (9,), seed=7)[0],
               "b": _turns(cfg, (13,), seed=8)[0]}
    want = {s: _dense_reference(cfg, model, params, [p])[0][0]
            for s, p in prompts.items()}
    reqs = {}
    for s, p in prompts.items():
        reqs[s] = InferenceRequest(session_id=s, prompt_tokens=len(p),
                                   max_new_tokens=GEN, prompt_ids=list(p))
        eng.submit(reqs[s])
    now = 0.0
    while eng.waiting or eng.running:
        now += eng.step(now)
    assert len(eng.running) == 0 and len(eng.completed) == 2
    for s in prompts:
        assert reqs[s].output_ids == want[s], s
