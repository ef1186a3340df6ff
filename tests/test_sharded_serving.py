"""Tensor-parallel sharded serving: token-exact parity on a CPU mesh.

One node = tp devices on a 1-D ``("model",)`` mesh: stacked KV pools get
the `ShardingPlan.pool_spec` NamedSharding (kv-heads -> ``model``, split-K
page-slot fallback for GQA), block weights get the Megatron column/row
specs, and every fused `step_paged` dispatch is a sharded jit.  The mesh
must be INVISIBLE to results and formats:

* token ids exactly equal the single-device serve at tp ∈ {1, 2, 4}, MHA
  and GQA (GQA at tp=4 exercises the split-K fallback — kv_heads=2 is not
  divisible by 4), including a preemption swap-out/swap-in round trip;
* prefix adoption + CoW forks work unchanged on a mesh;
* host payloads are pre-concatenated full-head numpy — a session exported
  at tp=2 imports at tp=4 (and the payload itself is shard-agnostic);
* the compile census keys on the mesh signature, so identical shape
  buckets at different tp count separately instead of colliding.

Runs on forced host devices (conftest.py appends
--xla_force_host_platform_device_count=8 to XLA_FLAGS).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.advisory import InferenceRequest
from repro.core.node_manager import NodeManager
from repro.distributed.sharding import ShardingPlan
from repro.launch.mesh import make_serving_mesh
from repro.models.registry import get_model
from repro.serving.backend import RealBackend
from repro.serving.cost_model import CostModel, HardwareSpec
from repro.serving.engine import NodeEngine

GEN = 4

needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs 4 forced host devices")
needs2 = pytest.mark.skipif(jax.device_count() < 2,
                            reason="needs 2 forced host devices")


def _cfg(kind: str):
    n_kv = dict(mha=4, gqa=2)[kind]
    return get_config("llama3-8b").reduced(dtype="float32", n_kv_heads=n_kv)


_MODELS = {}          # (kind, seed) -> (model, params): share jit caches
                      # across tests so the suite compiles each mesh once


def _model(cfg, kind, seed):
    if (kind, seed) not in _MODELS:
        model = get_model(cfg)
        _MODELS[(kind, seed)] = (model, model.init(jax.random.key(seed)))
    return _MODELS[(kind, seed)]


def _setup(kind: str, tp=None, seed: int = 0, **backend_kw):
    cfg = _cfg(kind)
    model, params = _model(cfg, kind, seed)
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    mesh = None if tp is None else make_serving_mesh(tp=tp)
    be = RealBackend(cfg, model, params, mgr=mgr, mesh=mesh,
                     **{**dict(n_pages=32, page_size=8), **backend_kw})
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=4, backend=be)
    return cfg, model, params, mgr, be, eng


def _turns(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(0, cfg.vocab, n))) for n in lens]


def _serve(eng, be, turns, gen=GEN, preempt_turn=None, sid="s0", cached=0):
    outs, now = [], 0.0
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
                eng.preempt_one(now)
                preempted = True
        outs.append(req.output_ids)
        cached = be.session_tokens(sid)
    return outs


# ---------------------------------------------------------------------------
# divisibility ladder: which pool/cache dim gets the model axis
# ---------------------------------------------------------------------------

@needs4
def test_pool_spec_divisibility_ladder():
    """(L, P+1, Hkv, page, D) pool: Hkv -> model when divisible, else the
    page-slot split-K fallback, else D, else replicate; the layer and
    page-index dims are never sharded (block tables are global)."""
    cfg = _cfg("mha")
    p2 = ShardingPlan(cfg, make_serving_mesh(tp=2))
    p4 = ShardingPlan(cfg, make_serving_mesh(tp=4))
    # Hkv=4 divides both
    assert p2.pool_spec((4, 33, 4, 8, 16)) == P(None, None, "model", None,
                                                None)
    assert p4.pool_spec((4, 33, 4, 8, 16)) == P(None, None, "model", None,
                                                None)
    # Hkv=2 at tp=4: split-K on the page-slot dim
    assert p4.pool_spec((4, 33, 2, 8, 16)) == P(None, None, None, "model",
                                                None)
    # page=6 indivisible too: the head-feature dim
    assert p4.pool_spec((4, 33, 2, 6, 16)) == P(None, None, None, None,
                                                "model")
    # nothing divisible: fully replicated
    assert p4.pool_spec((4, 33, 2, 6, 6)) == P(None, None, None, None, None)
    # same ladder in cache_spec's kv-like branch, on a model-only mesh
    # (no data axis present -> it must never name "data")
    assert p4.cache_spec("k", (4, 1, 8, 2, 16)) == P(None, None, "model",
                                                     None, None)
    assert p2.cache_spec("k", (4, 1, 8, 4, 16)) == P(None, None, None,
                                                     "model", None)


@needs2
def test_pool_sharding_places_one_shard_per_device():
    cfg = _cfg("mha")
    _, _, _, _, be, _ = _setup("mha", tp=2)
    assert be.tp == 2
    assert len(be.k_pool.sharding.device_set) == 2
    # per-device footprint is half the global pool
    assert be.pool_device_bytes() == be.k_pool.nbytes


# ---------------------------------------------------------------------------
# token-exact parity vs the single-device serve
# ---------------------------------------------------------------------------

def _single_device_reference(kind, turns, preempt_turn=None):
    _, _, _, _, be, eng = _setup(kind, tp=None)
    return _serve(eng, be, turns, preempt_turn=preempt_turn)


@needs4
@pytest.mark.parametrize("kind", ["mha", "gqa"])
def test_parity_across_tp_with_preemption(kind):
    """Multi-turn serve with a mid-decode preemption (swap-out/swap-in
    round trip through the sharded gather/scatter) must emit EXACTLY the
    single-device token ids at every tp.  GQA at tp=4 runs the split-K
    page-slot fallback (kv_heads=2 % 4 != 0)."""
    cfg = _cfg(kind)
    turns = _turns(cfg, (11, 7), seed=3)
    want = _single_device_reference(kind, turns, preempt_turn=1)
    for tp in (1, 2, 4):
        _, _, _, _, be, eng = _setup(kind, tp=tp)
        if kind == "gqa" and tp == 4:
            assert be._pool_sharding.spec == P(None, None, None, "model",
                                               None)
        got = _serve(eng, be, turns, preempt_turn=1)
        assert got == want, f"token divergence ({kind}, tp={tp})"
        assert be.stats["swaps_out"] >= 1 and be.stats["swaps_in"] >= 1


@needs4
def test_pallas_kernel_runs_per_shard_under_shard_map():
    """A Pallas kernel mode on a mesh runs the attention kernel per kv-head
    shard under `jax.shard_map` (a Mosaic kernel cannot be partitioned by
    the compiler): tp=2 interpret-mode serving, through a swap round trip
    and a quantized turn (the int8 kernel variant), emits exactly the
    single-device tokens.  A split-K pool has no per-shard kernel, so a
    Pallas mode there raises instead of serving from the oracle."""
    cfg = _cfg("mha")
    turns = _turns(cfg, (19, 5), seed=7)
    got = {}
    for tp in (None, 2):
        _, _, _, _, be, eng = _setup("mha", tp=tp, kernel_mode="interpret")
        assert be.kernel_mode == "interpret"
        outs = _serve(eng, be, turns[:1])
        be.swap_out("s0", be.session_tokens("s0"))
        be.drain_transfers()
        outs += _serve(eng, be, turns[1:], cached=be.session_tokens("s0"))
        assert be.quantize_session("s0") > 0
        outs += _serve(eng, be, turns[:1], cached=be.session_tokens("s0"))
        assert be.stats["swaps_in"] >= 1 and be.stats["quant_dispatches"]
        got[tp] = outs
    assert got[2] == got[None]
    with pytest.raises(ValueError, match="kv-heads"):
        _setup("gqa", tp=4, kernel_mode="interpret")


# ---------------------------------------------------------------------------
# prefix adoption + CoW on a mesh
# ---------------------------------------------------------------------------

@needs2
def test_prefix_adoption_and_cow_fork_tp2():
    """Donor completes, adopter diverges mid-page: the shared span must be
    adopted (no second prefill) and the CoW fork must run as a sharded
    donating dispatch — token ids exact for both."""
    shared = list(range(16))                  # two full pages
    pa, pb = shared + [100, 101, 102], shared + [100, 201, 202]
    want = {}
    _, _, _, _, be0, eng0 = _setup("gqa", tp=None)
    for sid, p in (("A", pa), ("B", pb)):
        req = InferenceRequest(session_id=sid, prompt_tokens=len(p),
                               max_new_tokens=GEN, prompt_ids=list(p))
        eng0.submit(req)
        now = 0.0
        while eng0.waiting or eng0.running:
            now += eng0.step(now)
        want[sid] = req.output_ids

    cfg, _, _, mgr, be, eng = _setup("gqa", tp=2)
    reqs = {sid: InferenceRequest(session_id=sid, prompt_tokens=len(p),
                                  max_new_tokens=GEN, prompt_ids=list(p))
            for sid, p in (("A", pa), ("B", pb))}
    now = 0.0
    eng.submit(reqs["A"])
    while eng.waiting or eng.running:
        now += eng.step(now)
    eng.submit(reqs["B"])                     # adopts A's indexed prefix
    while eng.waiting or eng.running:
        now += eng.step(now)
    for sid in reqs:
        assert reqs[sid].output_ids == want[sid], sid
    assert be.stats["prefix_hits"] == 1
    assert be.stats["cow_forks"] == cfg.n_layers   # mid-page divergence


# ---------------------------------------------------------------------------
# shard-count-agnostic host payloads: tp=2 -> tp=4 migration
# ---------------------------------------------------------------------------

@needs4
def test_export_at_tp2_import_at_tp4():
    """A session served and exported at tp=2 must resume token-exactly on
    a tp=4 node (and the payload itself is plain full-head numpy — no
    shard axis anywhere in the migration format)."""
    cfg = _cfg("mha")
    turns = _turns(cfg, (9, 6), seed=5)
    want = _single_device_reference("mha", turns)

    _, _, _, _, be2, eng2 = _setup("mha", tp=2)
    got = [_serve(eng2, be2, turns[:1])[0]]
    tokens = be2.session_tokens("s0")
    payload = be2.export_session("s0")
    assert payload is not None
    for l, p in payload["layers"].items():
        assert isinstance(p["k"], np.ndarray) and isinstance(p["v"],
                                                             np.ndarray)
        assert p["k"].shape[-2:] == (cfg.n_kv_heads, cfg.d_head)  # full heads

    _, _, _, mgr4, be4, eng4 = _setup("mha", tp=4)
    be4.import_session("s0", payload)
    mgr4.mark_resident("s0", tokens, be4.session_kv_bytes(tokens),
                       priority=0)
    got.append(_serve(eng4, be4, turns[1:], cached=tokens)[0])
    assert got == want
    assert be4.stats["migrations_in"] == 1


# ---------------------------------------------------------------------------
# mesh-keyed compile census
# ---------------------------------------------------------------------------

@needs2
def test_census_keys_on_mesh_signature():
    """Identical shape buckets served at tp=1-unsharded and tp=2 must count
    as DISTINCT census entries (two mesh placements really are two XLA
    programs), and re-serving the same shapes at the same tp must add
    nothing (the recompile-free steady state per mesh)."""
    cfg = _cfg("mha")
    turns = _turns(cfg, (9,), seed=11)
    _, model, _, _, be_a, eng_a = _setup("mha", seed=13, tp=None)
    _serve(eng_a, be_a, turns)
    base = be_a.compile_counts()["step"]
    assert base >= 1
    _, _, _, _, be_b, eng_b = _setup("mha", seed=13, tp=2)
    _serve(eng_b, be_b, turns)
    assert be_b.compile_counts()["step"] == 2 * base   # no collision
    _, _, _, _, be_c, eng_c = _setup("mha", seed=13, tp=2)
    _serve(eng_c, be_c, turns)
    assert be_c.compile_counts()["step"] == 2 * base   # steady state
