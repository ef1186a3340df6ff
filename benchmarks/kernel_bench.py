"""Kernel micro-bench harness.  On CPU the Pallas kernels execute in
interpret mode, so the us_per_call column is NOT TPU performance — the
derived column carries the analytic VMEM working set + arithmetic intensity
the roofline uses; on a real TPU the same harness times the compiled kernel.

``bench_step`` is the serving-level companion: a steady-state serving step
(1 prefill + N decode steps) through the RealBackend's fused bucketed
dispatch, at two batch sizes and two turn lengths, plus the long-prompt
INTERFERENCE mode: a 4k-token prompt arriving mid-decode chunks through the
unified token-budget step while the running decode lanes keep emitting one
token per iteration — p99 time-between-tokens for those lanes must stay
within a small factor of the steady-state decode step (before the unified
step, the monolithic prefill stalled every lane for the whole prompt), with
zero compilations during the measured pass (all shape buckets are warmed by
an identical pass first).  Everything lands in
``results/bench/BENCH_step.json`` — per-decode-step latency, fused-step
compile counts, copied bytes, and the interference TBT profile — the
perf-trajectory artifact CI uploads and bounds (unbounded recompilation or
a TBT-bound regression fails the workflow).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def bench_kernels():
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.flash_prefill import flash_prefill
    rng = np.random.default_rng(0)
    interp = jax.default_backend() != "tpu"

    # paged decode: llama3-8b-like geometry (reduced B for interpret mode)
    B, H, Hkv, D, page, maxp = 4, 32, 8, 128, 16, 8
    P = B * maxp + 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(P, Hkv, page, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, page, D)), jnp.bfloat16)
    tab = jnp.asarray(rng.integers(0, P, (B, maxp)), jnp.int32)
    ctx = jnp.full((B,), maxp * page, jnp.int32)
    us = _time(lambda *a: paged_attention(*a, interpret=interp),
               q, kp, vp, tab, ctx)
    vmem_kb = (page * D * 2 * 2 + (H // Hkv) * D * (2 + 4 * 3)) / 1024
    flops = 4 * B * H * D * maxp * page
    emit("kernel.paged_attention.us", us,
         f"interpret={interp} vmem_tile={vmem_kb:.0f}KB flops={flops:.2e}")

    # flash prefill: 64 cached + 64 new
    Bq, S1, S2 = 2, 64, 64
    qq = jnp.asarray(rng.normal(size=(Bq, S2, H, D)), jnp.bfloat16)
    kk = jnp.asarray(rng.normal(size=(Bq, S1 + S2, Hkv, D)), jnp.bfloat16)
    vv = jnp.asarray(rng.normal(size=(Bq, S1 + S2, Hkv, D)), jnp.bfloat16)
    us = _time(lambda *a: flash_prefill(*a, q_offset=S1, bq=32, bk=32,
                                        interpret=interp), qq, kk, vv)
    emit("kernel.flash_prefill.us", us,
         f"interpret={interp} continuation 64+64, bq=bk=32")

    # SSD chunk scan: zamba2-like geometry (reduced for interpret mode)
    from repro.kernels.ssd_scan import ssd_scan
    B2, S2s, H2, P2, N2 = 2, 256, 4, 32, 16
    x = jnp.asarray(rng.normal(size=(B2, S2s, H2, P2)), jnp.bfloat16)
    dA = jnp.asarray(-np.abs(rng.normal(scale=0.1, size=(B2, S2s, H2))),
                     jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B2, S2s, H2, N2)), jnp.bfloat16)
    Cm = jnp.asarray(rng.normal(size=(B2, S2s, H2, N2)), jnp.bfloat16)
    us = _time(lambda *a: ssd_scan(*a, chunk=64, interpret=interp),
               x, dA, Bm, Cm)
    emit("kernel.ssd_scan.us", us,
         f"interpret={interp} S={S2s} chunk=64 state={N2}x{P2} in VMEM")


def bench_step(decode_steps: int = 16):
    """Steady-state serving-step bench through RealBackend (fused bucketed
    dispatch, trace_logits off): 1 prefill + ``decode_steps`` decode steps
    at two batch sizes x two turn lengths.  Steps that paid a shape-bucket
    compile (the compile census advanced during the step) are counted but
    excluded from the latency stats, so decode_ms_* tracks the recompile-
    free hot path rather than one-off interpret-mode compile time."""
    from repro.configs import get_config
    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.models.registry import get_model
    from repro.serving.backend import RealBackend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine

    cfg = get_config("llama3-8b").reduced(dtype="float32")
    model = get_model(cfg)                   # shared: jit cache == bucket set
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    payload = dict(decode_steps=decode_steps, configs={})
    for B, plen in ((1, 12), (2, 12), (1, 21), (2, 21)):
        cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
        cost.set_param_count(model.param_count())
        mgr = NodeManager(0, cfg, cost)
        be = RealBackend(cfg, model, params, n_pages=64, page_size=8,
                         mgr=mgr, trace_logits=False)
        eng = NodeEngine(0, cfg, cost, mgr, max_batch=8, backend=be)
        for i in range(B):
            prompt = list(map(int, rng.integers(0, cfg.vocab, plen)))
            eng.submit(InferenceRequest(
                session_id=f"s{i}", prompt_tokens=plen,
                max_new_tokens=decode_steps + 1, prompt_ids=prompt))
        now, steps, compiled = 0.0, [], []
        t0 = time.perf_counter()
        while eng.waiting or eng.running:
            s0 = time.perf_counter()
            census = be.compile_counts()
            now += eng.step(now)
            steps.append(time.perf_counter() - s0)
            compiled.append(be.compile_counts() != census)
        wall = time.perf_counter() - t0
        # step 0 carries the prefill; compile-paying steps are excluded from
        # the latency stats (reported separately) so the numbers track the
        # recompile-free hot path
        dsteps = np.asarray([s for s, c in zip(steps[1:], compiled[1:])
                             if not c])
        # every step paying a compile leaves no steady state to report; use
        # null (valid strict JSON) rather than NaN for those stats
        ms = lambda x: float(x * 1e3) if dsteps.size else None
        key = f"B{B}_plen{plen}"
        payload["configs"][key] = dict(
            batch=B, turn_len=plen, wall_s=wall,
            steady_steps=int(dsteps.size),
            compile_steps=int(sum(compiled)),
            decode_ms_mean=ms(dsteps.mean() if dsteps.size else 0),
            decode_ms_median=ms(np.median(dsteps) if dsteps.size else 0),
            decode_ms_p90=ms(np.percentile(dsteps, 90) if dsteps.size else 0),
            copied_bytes=be.stats["copied_bytes"],
            compile_counts=be.compile_counts())   # cumulative across configs
        cc = be.compile_counts()
        emit(f"step.{key}.decode_ms",
             float(dsteps.mean() * 1e3) if dsteps.size else float("nan"),
             f"steady_steps={dsteps.size} "
             f"compile_steps={int(sum(compiled))} "
             f"compiles=s{cc['step']}")
    payload["compile_counts"] = model.paged_compile_counts()
    payload["interference"] = bench_interference()
    payload["overlap"] = bench_overlap()
    payload["mesh"] = bench_mesh()
    save("BENCH_step", payload)
    return payload


def bench_mesh(tp: int = 2, decode_steps: int = 16):
    """Tensor-parallel serving-step mode: the sharded-node observables.

    Serves the same steady-state conversation twice through one shared
    model — unsharded, then on a ``("model",)`` mesh of ``tp`` devices —
    and reports per-device pool bytes (must be ~1/tp of the single-device
    pool), steady-state decode latency for both, and the mesh-keyed
    compile census.  Each mesh placement is warmed by an identical pass
    first, so the measured pass must stay at ZERO compiles (the CI gate).
    Requires ``tp`` visible devices and runs in this process (a child
    process could not reach a chip the parent already holds) — on CPU run
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``; with too
    few devices `make_serving_mesh` raises and the bench fails."""
    from repro.configs import get_config
    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.launch.mesh import make_serving_mesh
    from repro.models.registry import get_model
    from repro.serving.backend import RealBackend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine

    cfg = get_config("llama3-8b").reduced(dtype="float32", n_kv_heads=4)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))

    def serve(mesh, measure):
        rng = np.random.default_rng(0)
        cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
        cost.set_param_count(model.param_count())
        mgr = NodeManager(0, cfg, cost)
        be = RealBackend(cfg, model, params, n_pages=64, page_size=8,
                         mgr=mgr, trace_logits=False, mesh=mesh)
        eng = NodeEngine(0, cfg, cost, mgr, max_batch=8, backend=be)
        for i in range(2):
            prompt = list(map(int, rng.integers(0, cfg.vocab, 12)))
            eng.submit(InferenceRequest(
                session_id=f"s{i}", prompt_tokens=12,
                max_new_tokens=decode_steps + 1, prompt_ids=prompt))
        now, steps, compiles = 0.0, [], 0
        while eng.waiting or eng.running:
            s0 = time.perf_counter()
            census = be.compile_counts()
            now += eng.step(now)
            steps.append(time.perf_counter() - s0)
            compiles += be.compile_counts() != census
        if not measure:
            return dict(warm_compiles=compiles), be
        dsteps = np.asarray(steps[1:])
        return dict(decode_ms_mean=float(dsteps.mean() * 1e3),
                    decode_ms_median=float(np.median(dsteps) * 1e3),
                    measured_compiles=compiles), be

    out = dict(tp=tp, devices=jax.device_count())
    serve(None, measure=False)                       # warm single-device
    single, be1 = serve(None, measure=True)
    out["single_device"] = dict(**single,
                                pool_device_bytes=be1.pool_device_bytes())
    mesh = make_serving_mesh(tp=tp)
    warm, be_w = serve(mesh, measure=False)          # warm this placement
    meshed, be_m = serve(mesh, measure=True)
    out["meshed"] = dict(**meshed,
                         pool_device_bytes=be_m.pool_device_bytes(),
                         pool_spec=str(be_m._pool_sharding.spec),
                         warm_compiles=warm["warm_compiles"])
    out["pool_bytes_ratio"] = (out["meshed"]["pool_device_bytes"]
                               / out["single_device"]["pool_device_bytes"])
    out["compile_counts"] = model.paged_compile_counts()
    emit(f"mesh.tp{tp}.decode_ms", out["meshed"]["decode_ms_mean"],
         f"single={out['single_device']['decode_ms_mean']:.2f}ms "
         f"pool_ratio={out['pool_bytes_ratio']:.3f} "
         f"measured_compiles={out['meshed']['measured_compiles']}")
    save("BENCH_mesh", out)
    return out


def bench_overlap(ctx_len: int = 1536, lead_steps: int = 4,
                  kernel_mode: str = None):
    """Swap-in overlap mode: the async-transfer-engine observable.

    A session with ``ctx_len`` tokens of KV sits swapped out in the host
    tier while two decode lanes keep the node busy.  Its next turn is then
    served two ways:

    * COLD — no advisory: the admitting step itself launches the
      host->device scatter and immediately fences it, so the full copy
      (staging + transfer + scatter) lands in ``stats["stall_s"]``;
    * WARM — an advisory prefetch (`NodeManager.promote`) launches the
      same copy ``lead_steps`` decode iterations BEFORE the turn arrives;
      the transfer drains under the interleaved compute and the admitting
      step only fences an already-completed future — the measured stall is
      the *residual*, which must be ~0.

    ``overlap_ratio`` = 1 - warm/cold is the fraction of the swap-in copy
    the advisory moved off the critical serving path; CI gates the warm
    residual at ~0 (<= max(25% of cold, 5 ms))."""
    from repro.configs import get_config
    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.models.registry import get_model
    from repro.serving.backend import RealBackend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine

    if kernel_mode is None:
        kernel_mode = "auto" if jax.default_backend() == "tpu" else "ref"
    cfg = get_config("llama3-8b").reduced(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    page_size = 16
    bg_gen = 640                 # decode lanes outlive every phase
    n_pages = (ctx_len + 64) // page_size \
        + 2 * (bg_gen + 16) // page_size + 24
    be = RealBackend(cfg, model, params, n_pages=n_pages,
                     page_size=page_size, mgr=mgr, trace_logits=False,
                     kernel_mode=kernel_mode)
    # budget 255: a 255-token chunk + the pending token fills the Sq=256
    # bucket exactly, so building the context costs few compiles
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=8, backend=be,
                     token_budget=255)
    rng = np.random.default_rng(0)
    state = dict(now=0.0)

    def step():
        state["now"] += eng.step(state["now"])

    def serve(sid, plen, gen=8):
        p = list(map(int, rng.integers(0, cfg.vocab, plen)))
        eng.submit(InferenceRequest(
            session_id=sid, prompt_tokens=plen, max_new_tokens=gen,
            prompt_ids=p, cached_tokens=be.session_tokens(sid)))
        while (any(r.req.session_id == sid for r in eng.running)
               or sid in [r.session_id for r in eng.waiting]):
            step()

    # two persistent decode lanes keep compute flowing between phases
    for i in range(2):
        p = list(map(int, rng.integers(0, cfg.vocab, 12)))
        eng.submit(InferenceRequest(session_id=f"d{i}", prompt_tokens=12,
                                    max_new_tokens=bg_gen, prompt_ids=p))
    for _ in range(6):
        step()

    serve("vip", ctx_len)                      # build ctx_len tokens of KV
    # warm every bucket the measured turns will touch (incl. the swap-in
    # scatter), so neither phase pays one-off compiles
    be.swap_out("vip", be.session_tokens("vip"))
    be.drain_transfers()
    serve("vip", 8)

    def phase(advisory_lead: int):
        be.swap_out("vip", be.session_tokens("vip"))
        be.drain_transfers()                   # KV fully in the host tier
        base_stall, base_busy = eng.stats["stall_s"], eng.stats["busy_s"]
        if advisory_lead:
            mgr.promote("vip", now=state["now"])   # enqueue the prefetch
            for _ in range(advisory_lead):
                step()                         # copy drains under decode
        serve("vip", 8)
        return (eng.stats["stall_s"] - base_stall,
                eng.stats["busy_s"] - base_busy)

    census0 = be.compile_counts()
    cold_stall, cold_busy = phase(advisory_lead=0)
    warm_stall, warm_busy = phase(advisory_lead=lead_steps)
    measured_compiles = {k: be.compile_counts()[k] - census0.get(k, 0)
                         for k in be.compile_counts()}

    out = dict(
        ctx_len=ctx_len, lead_steps=lead_steps, kernel_mode=kernel_mode,
        stall_cold_ms=cold_stall * 1e3,
        stall_warm_ms=warm_stall * 1e3,
        stall_cold_frac=cold_stall / max(cold_busy, 1e-12),
        stall_warm_frac=warm_stall / max(warm_busy, 1e-12),
        overlap_ratio=1.0 - warm_stall / max(cold_stall, 1e-12),
        measured_compiles=sum(measured_compiles.values()),
        transfers=dict(be.transfers.stats),
        prefetched_layers=mgr.stats["promoted_layers"],
        compile_counts=dict(be.compile_counts()),
    )
    emit("step.overlap.stall_warm_ms", out["stall_warm_ms"],
         f"cold={out['stall_cold_ms']:.2f}ms "
         f"overlap_ratio={out['overlap_ratio']:.3f} "
         f"ctx={ctx_len} lead={lead_steps} "
         f"compiles_measured={out['measured_compiles']}")
    return out


def bench_interference(prompt_len: int = 4000, token_budget: int = 4,
                       kernel_mode: str = None):
    """Long-prompt interference: a ~4k-token prompt arrives while two lanes
    decode.  The token-budget scheduler chunks it through the SAME fused
    steps the decode lanes ride, so every iteration still emits one token
    per running lane — the measured series IS their time-between-tokens.

    Protocol: (1) steady decode baseline; (2) a WARM pass serves an
    identically-shaped long prompt to completion, compiling every
    (lanes, tokens-per-step, table-width) bucket the interference will
    touch; (3) the measured pass re-runs it against warm caches — zero
    compilations expected (``interference_compiles`` records the truth) —
    and (4) the long session's own decode phase at FULL context, which is
    the context-matched steady-state decode the TBT bound is measured
    against.  The headline is ``tbt_p99_over_steady_p99``: chunk-step p99
    over steady-decode p99, SAME-percentile so shared-host scheduling
    noise (which lands on both distributions identically) cancels; on
    quiet hardware steady p99 ~= steady median and this converges to the
    strict "p99 TBT <= k x steady decode step" reading.  Bounded by the
    budget — the pre-unified-step engine dispatched the whole prompt as
    one monolithic prefill, and the ratio was the prompt length.

    The reduced CPU config runs the pure-jnp kernel oracle by default
    (``kernel_mode="ref"``) — interpret-mode Pallas emulation walks the
    page grid in software and would time the emulator, not the serving
    path; on a TPU the compiled kernels are the real path (``auto``).
    ``token_budget=4`` is the reduced-model scaling of Sarathi-class
    256-512-token budgets (d_model 64 vs 4096): the budget is chosen so a
    mixed step costs a small multiple of a context-matched decode step."""
    from repro.configs import get_config
    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.models.registry import get_model
    from repro.serving.backend import RealBackend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine

    if kernel_mode is None:
        kernel_mode = "auto" if jax.default_backend() == "tpu" else "ref"
    cfg = get_config("llama3-8b").reduced(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    page_size = 16
    # the two decode lanes must OUTLIVE the warm + measured passes
    # (~2 * prompt_len/budget steps) or the lane-count bucket drifts
    # mid-measurement; size their generation budget and the pool for that
    lane_gen = 2 * prompt_len // token_budget + 400
    n_pages = (prompt_len + 64) // page_size \
        + 2 * (lane_gen + 16) // page_size + 24
    be = RealBackend(cfg, model, params, n_pages=n_pages,
                     page_size=page_size, mgr=mgr, trace_logits=False,
                     kernel_mode=kernel_mode)
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=8, backend=be,
                     token_budget=token_budget)
    rng = np.random.default_rng(0)
    state = dict(now=0.0)

    def step_timed():
        t0 = time.perf_counter()
        state["now"] += eng.step(state["now"])
        return time.perf_counter() - t0

    def serve_long(sid):
        """Submit a long prompt and serve the session to completion.
        Returns (chunk_steps, chunk_compiled, decode_steps): the steps
        while its prompt chunks through, then the steps while it decodes
        at FULL context — the context-matched steady-state decode the TBT
        bound is measured against (same lane count, same table bucket)."""
        prompt = list(map(int, rng.integers(0, cfg.vocab, prompt_len)))
        eng.submit(InferenceRequest(session_id=sid,
                                    prompt_tokens=prompt_len,
                                    max_new_tokens=64, prompt_ids=prompt))
        chunk_ts, chunk_compiled, dec_ts = [], [], []
        while (any(r.req.session_id == sid for r in eng.running)
               or sid in [r.session_id for r in eng.waiting]):
            prefilling = any(r.req.session_id == sid and r.prompt_left > 0
                             for r in eng.running) \
                or sid in [r.session_id for r in eng.waiting]
            census = be.compile_counts()["step"]
            dt = step_timed()
            advanced = be.compile_counts()["step"] != census
            if prefilling:
                chunk_ts.append(dt)
                chunk_compiled.append(advanced)
            elif not advanced:
                dec_ts.append(dt)
        return chunk_ts, chunk_compiled, dec_ts

    def steady_decode(n):
        """n decode-only steps; census-advancing ones are dropped."""
        ts = []
        for _ in range(n):
            census = be.compile_counts()["step"]
            dt = step_timed()
            if be.compile_counts()["step"] == census:
                ts.append(dt)
        return ts

    # two persistent decode lanes: they outlive both passes (keeping the
    # lane-count bucket stable), sized so admission's KV headroom check
    # still passes alongside the long prompt
    for i in range(2):
        p = list(map(int, rng.integers(0, cfg.vocab, 12)))
        eng.submit(InferenceRequest(session_id=f"d{i}", prompt_tokens=12,
                                    max_new_tokens=lane_gen, prompt_ids=p))
    for _ in range(6):
        step_timed()
    pre = steady_decode(12)

    warm = serve_long("warm")                          # compiles the buckets
    mgr.drop_session("warm")                           # free its pages

    census0 = be.compile_counts()["step"]
    chunk_ts, chunk_compiled, dec_ts = serve_long("long")   # warm caches
    interference_compiles = be.compile_counts()["step"] - census0
    idle = steady_decode(12)                           # long gone again

    tbt = np.asarray([t for t, c in zip(chunk_ts, chunk_compiled)
                      if not c]) * 1e3
    steady = np.asarray(dec_ts) * 1e3
    steady_median = float(np.median(steady))
    steady_p99 = float(np.percentile(steady, 99))
    out = dict(
        prompt_len=prompt_len, token_budget=token_budget,
        kernel_mode=kernel_mode,
        steps=len(chunk_ts),
        steady_pre_ms=float(np.median(pre) * 1e3) if pre else None,
        steady_idle_ms=float(np.median(idle) * 1e3) if idle else None,
        steady_median_ms=steady_median,
        steady_p99_ms=steady_p99,
        tbt_median_ms=float(np.median(tbt)),
        tbt_p90_ms=float(np.percentile(tbt, 90)),
        tbt_p99_ms=float(np.percentile(tbt, 99)),
        tbt_max_ms=float(tbt.max()),
        tbt_median_over_steady=float(np.median(tbt) / steady_median),
        tbt_p99_over_steady_p99=float(np.percentile(tbt, 99) / steady_p99),
        interference_compiles=int(interference_compiles),
        warm_compile_steps=int(sum(warm[1])),
        compile_counts=dict(model.paged_compile_counts()),
    )
    emit("step.interference.tbt_p99_ms", out["tbt_p99_ms"],
         f"steady_p99={steady_p99:.2f}ms ratio_p99="
         f"{out['tbt_p99_over_steady_p99']:.2f} ratio_median="
         f"{out['tbt_median_over_steady']:.2f} "
         f"compiles_measured={interference_compiles} "
         f"budget={token_budget} prompt={prompt_len}")
    return out


def bench_hetero(long_len: int = 200, decode_steps: int = 64,
                 kernel_mode: str = None):
    """Heterogeneous-skew mode: the page-walk-elimination observable.

    One resumed long-context lane (``long_len`` tokens) decodes alongside
    15 short lanes — SYMPHONY's signature multi-turn batch shape.  Before
    page-walk elimination every short lane's attention was padded to the
    long lane's table-width bucket, so one straggler repriced the whole
    batch; with context-aware lane packing the step splits into two
    sub-dispatches on the bucket lattice and each lane walks only its own
    relevant pages.

    Protocol: each scenario runs TWICE against the same model object — a
    warm pass compiles every shape bucket, then a fresh backend re-serves
    the identical scenario and only its decode-phase steps are timed
    (``compiles`` records the census delta across the measured window;
    the CI gate requires 0).  The headline is ``p99_ratio``: skewed-batch
    decode p99 over a context-matched homogeneous baseline (the same 15
    short lanes plus a 16th short lane instead of the long one) — SAME
    percentile on both sides so shared-host scheduling noise cancels.
    ``dma_pages``/``grid_pages`` come from the backend's page-walk
    counters: the pages a lane actually fetches vs the grid walked, and
    ``fused_grid_pages`` is what the pre-split dispatch would have walked
    (every lane padded to the long lane's bucket).

    Like the other serving modes this times the pure-jnp oracle on CPU
    (``kernel_mode="ref"``) — interpret-mode Pallas would time the
    emulator — and the compiled kernels on a TPU (``auto``)."""
    from repro.configs import get_config
    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.models.registry import get_model
    from repro.serving.backend import RealBackend, _bucket
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine

    if kernel_mode is None:
        kernel_mode = "auto" if jax.default_backend() == "tpu" else "ref"
    cfg = get_config("llama3-8b").reduced(dtype="float32")
    model = get_model(cfg)                  # shared: jit cache == bucket set
    params = model.init(jax.random.key(0))
    page_size = 8
    shorts = [6, 7, 8, 9, 10, 11, 12, 9, 8, 7, 6, 10, 11, 12, 9]
    n_pages = (long_len + decode_steps) // page_size \
        + 16 * (max(shorts) + decode_steps) // page_size + 32

    def run(prompt_lens, seed=3):
        """Serve the scenario to completion on a FRESH backend; time only
        the decode phase (every lane past its prompt) and return latency
        stats plus the page-walk counter deltas over that window."""
        cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
        cost.set_param_count(model.param_count())
        mgr = NodeManager(0, cfg, cost)
        be = RealBackend(cfg, model, params, n_pages=n_pages,
                         page_size=page_size, mgr=mgr, trace_logits=False,
                         kernel_mode=kernel_mode)
        eng = NodeEngine(0, cfg, cost, mgr, max_batch=16, backend=be,
                         token_budget=512)
        rng = np.random.default_rng(seed)
        for i, n in enumerate(prompt_lens):
            p = list(map(int, rng.integers(0, cfg.vocab, n)))
            eng.submit(InferenceRequest(session_id=f"s{i}", prompt_tokens=n,
                                        max_new_tokens=decode_steps,
                                        prompt_ids=p))
        now = 0.0
        while eng.waiting or any(r.prompt_left > 0 for r in eng.running):
            now += eng.step(now)            # prefill phase: not timed
        snap = dict(be.stats)
        census0 = be.compile_counts()["step"]
        ts = []
        while eng.running:
            t0 = time.perf_counter()
            now += eng.step(now)
            ts.append(time.perf_counter() - t0)
        ts = np.asarray(ts) * 1e3
        d = {k: be.stats[k] - snap[k]
             for k in ("dma_pages", "grid_pages", "sub_dispatches",
                       "split_steps", "decode_steps")}
        return dict(
            steps=len(ts),
            p50_ms=float(np.median(ts)),
            p99_ms=float(np.percentile(ts, 99)),
            compiles=int(be.compile_counts()["step"] - census0),
            dma_pages_per_step=d["dma_pages"] / max(len(ts), 1),
            **d)

    skew_lens = [long_len] + shorts
    homog_lens = shorts + [shorts[0]]       # context-matched short baseline
    run(skew_lens)                          # warm: compiles skew buckets
    run(homog_lens)                         # warm: compiles homog buckets
    skew = run(skew_lens)
    homog = run(homog_lens)

    # what one fused dispatch per decode step would have walked: every lane
    # padded to the long lane's table-width bucket
    long_pages = -(-(long_len + decode_steps) // page_size)
    fused_grid = skew["decode_steps"] * _bucket(16) * _bucket(long_pages)
    out = dict(
        long_len=long_len, shorts=shorts, decode_steps=decode_steps,
        page_size=page_size, kernel_mode=kernel_mode,
        skew=skew, homog=homog,
        p99_ratio=skew["p99_ms"] / homog["p99_ms"],
        p50_ratio=skew["p50_ms"] / homog["p50_ms"],
        fused_grid_pages=int(fused_grid),
        grid_over_fused=skew["grid_pages"] / max(fused_grid, 1),
        measured_compiles=skew["compiles"] + homog["compiles"],
    )
    emit("step.hetero.p99_ratio", out["p99_ratio"],
         f"skew_p99={skew['p99_ms']:.2f}ms homog_p99={homog['p99_ms']:.2f}ms "
         f"dma_pages/step={skew['dma_pages_per_step']:.1f} "
         f"grid_over_fused={out['grid_over_fused']:.2f} "
         f"splits={skew['split_steps']} "
         f"compiles_measured={out['measured_compiles']}")
    save("BENCH_hetero", out)
    return out


def bench_sharing(n_sessions: int = 1000, shared_len: int = 64,
                  suffix_len: int = 3, gen: int = 2,
                  kernel_mode: str = None):
    """Prefix-sharing mode: the copy-on-write observable.

    ``n_sessions`` single-turn sessions all carry the same ``shared_len``
    system prompt plus a private ``suffix_len`` tail (the multi-tenant
    workload prefix sharing targets).  The first session is served alone —
    its pages become the cohort's indexed prefix — then the rest stream
    through the engine, each adopting the shared span at admission instead
    of prefilling it.  The headline is ``footprint_ratio``: peak physical
    pages over the unshared ``n_sessions * pages_for(full context)`` cost.
    Shared pages are counted ONCE however many sessions reference them, so
    the footprint must stay SUBLINEAR in sessions — ~(shared_pages +
    n_sessions * suffix_pages) / (n_sessions * total_pages), far below the
    0.5 CI gate at these shapes.  ``parity_ok`` spot-checks a few cohort
    members token-for-token against the dense reference: sharing must be a
    pure memory optimization, never a decode change."""
    from repro.configs import get_config
    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.models.registry import get_model
    from repro.serving.backend import RealBackend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine

    if kernel_mode is None:
        kernel_mode = "auto" if jax.default_backend() == "tpu" else "ref"
    cfg = get_config("llama3-8b").reduced(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    page_size = 16
    total_tok = shared_len + suffix_len + gen
    pages_each = -(-total_tok // page_size)
    shared_pages = -(-shared_len // page_size)
    # shared prefix once + one private tail page per session + headroom
    n_pages = shared_pages + n_sessions * (pages_each - shared_pages) + 64
    be = RealBackend(cfg, model, params, n_pages=n_pages,
                     page_size=page_size, mgr=mgr, trace_logits=False,
                     kernel_mode=kernel_mode)
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=16, backend=be)
    rng = np.random.default_rng(0)
    shared = list(map(int, rng.integers(0, cfg.vocab, shared_len)))
    sids = [f"u{i:04d}" for i in range(n_sessions)]
    prompts = {sid: shared + list(map(int, rng.integers(0, cfg.vocab,
                                                        suffix_len)))
               for sid in sids}
    reqs = {sid: InferenceRequest(session_id=sid,
                                  prompt_tokens=len(prompts[sid]),
                                  max_new_tokens=gen,
                                  prompt_ids=list(prompts[sid]))
            for sid in sids}
    state = dict(now=0.0, peak=0)

    def pump():
        state["now"] += eng.step(state["now"])
        state["peak"] = max(state["peak"], be.alloc[0].used_pages)

    t0 = time.perf_counter()
    eng.submit(reqs[sids[0]])            # the donor registers the prefix
    while eng.waiting or eng.running:
        pump()
    for sid in sids[1:]:
        eng.submit(reqs[sid])
    while eng.waiting or eng.running:
        pump()
    wall = time.perf_counter() - t0

    # dense-reference parity spot-check on a few cohort members
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    parity_ok = True
    for sid in (sids[0], sids[1], sids[n_sessions // 2], sids[-1]):
        logits, cache = prefill(params,
                                jnp.asarray([prompts[sid]], jnp.int32))
        cache = model.grow_cache(cache, gen)
        outs = []
        for _ in range(gen):
            nxt = jnp.argmax(logits[:, :cfg.vocab], -1).astype(jnp.int32)
            outs.append(int(nxt[0]))
            logits, cache = decode(params, cache, nxt)
        parity_ok = parity_ok and (reqs[sid].output_ids == outs)

    unshared_pages = n_sessions * pages_each
    out = dict(
        n_sessions=n_sessions, shared_len=shared_len,
        suffix_len=suffix_len, gen=gen, page_size=page_size,
        kernel_mode=kernel_mode, pool_pages=n_pages,
        peak_used_pages=state["peak"],
        final_used_pages=be.alloc[0].used_pages,
        unshared_pages=unshared_pages,
        footprint_ratio=state["peak"] / unshared_pages,
        prefix_hits=be.stats["prefix_hits"],
        shared_tokens=be.stats["shared_tokens"],
        cow_forks=be.stats["cow_forks"],
        prefill_tokens=eng.stats["prefill_tokens"],
        shared_prefix_tokens=eng.stats["shared_prefix_tokens"],
        parity_ok=bool(parity_ok),
        wall_s=wall,
    )
    emit("step.sharing.footprint_ratio", out["footprint_ratio"],
         f"peak={state['peak']}p vs unshared={unshared_pages}p "
         f"sessions={n_sessions} hits={out['prefix_hits']} "
         f"shared_tok={out['shared_tokens']} parity_ok={parity_ok}")
    save("BENCH_sharing", out)
    return out


def bench_recurrent(ctx_len: int = 768, gen: int = 8,
                    headroom_tokens: int = 4096, kernel_mode: str = None):
    """Recurrent-state mode: the SYMPHONY "cheapest migration" observable.

    A session with ``ctx_len`` tokens of context is swapped to the host
    tier and cold-resumed on two node kinds at the same reduced scale:

    * KV — llama3-8b through `RealBackend`: the swap-in scatters
      O(ctx_len) paged KV bytes, and the admitting step's fence pays for
      the full linear copy;
    * recurrent — mamba2-2.7b through `StateBackend`: the whole session is
      ONE fixed-size slot blob, so the copy (and the stall) is O(1) — the
      same bytes at any context length.

    The headline pair is ``swap_bytes_ratio`` (KV bytes over state bytes at
    ``ctx_len`` — grows with context by construction) and the analytic
    ``sessions_per_node`` headroom at FULL model scale on equal hardware:
    HBM-resident sessions of ``headroom_tokens`` context each, where the
    recurrent family's O(1) state admits a multiple of the transformer's
    linear-KV count.  ``parity_ok`` serves a short multi-turn mamba2
    conversation through the engine with a swap round trip between turns
    and must match the dense reference token-for-token — the bench is only
    meaningful while the slot path is exact."""
    from repro.configs import get_config
    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.models.registry import get_model
    from repro.serving.backend import make_backend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine
    from repro.serving.scenario import dense_reference

    if kernel_mode is None:
        kernel_mode = "auto" if jax.default_backend() == "tpu" else "ref"

    def _node(arch, seed=0, **kw):
        cfg = get_config(arch).reduced(dtype="float32")
        model = get_model(cfg)
        params = model.init(jax.random.key(seed))
        cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
        cost.set_param_count(model.param_count())
        mgr = NodeManager(0, cfg, cost)
        be = make_backend(cfg, model, params, mgr=mgr, trace_logits=False,
                          kernel_mode=kernel_mode, **kw)
        eng = NodeEngine(0, cfg, cost, mgr, max_batch=8, backend=be,
                         token_budget=255)
        return cfg, model, params, mgr, be, eng

    def _cold_resume(arch, **kw):
        """Build ctx_len tokens, warm every bucket (incl. the swap-in
        scatter), then measure the cold-resume fence stall + copied bytes."""
        cfg, model, params, mgr, be, eng = _node(arch, **kw)
        rng = np.random.default_rng(0)
        state = dict(now=0.0)

        def serve(sid, plen, g=gen):
            p = list(map(int, rng.integers(0, cfg.vocab, plen)))
            eng.submit(InferenceRequest(
                session_id=sid, prompt_tokens=plen, max_new_tokens=g,
                prompt_ids=p, cached_tokens=be.session_tokens(sid)))
            while (any(r.req.session_id == sid for r in eng.running)
                   or sid in [r.session_id for r in eng.waiting]):
                state["now"] += eng.step(state["now"])

        serve("vip", ctx_len)
        be.swap_out("vip", be.session_tokens("vip"))
        be.drain_transfers()
        serve("vip", 8)                       # warm the swap-in buckets
        be.swap_out("vip", be.session_tokens("vip"))
        be.drain_transfers()
        base_stall = eng.stats["stall_s"]
        base_copied = be.stats["copied_bytes"]
        t0 = time.perf_counter()
        serve("vip", 8)                       # COLD resume: fence pays all
        wall = time.perf_counter() - t0
        n = be.session_tokens("vip")
        return dict(
            arch=arch,
            stall_cold_ms=(eng.stats["stall_s"] - base_stall) * 1e3,
            resume_wall_ms=wall * 1e3,
            swap_in_bytes=be.stats["copied_bytes"] - base_copied,
            resident_bytes=be.session_kv_bytes(n),
            resident_bytes_half_ctx=be.session_kv_bytes(n // 2),
            session_tokens=n,
            swaps_in=be.stats["swaps_in"],
        )

    kv = _cold_resume("llama3-8b",
                      n_pages=(ctx_len + 128) // 16 + 24, page_size=16)
    rec = _cold_resume("mamba2-2.7b", n_slots=4)

    # engine-level parity with a swap round trip between turns: the bench's
    # own correctness spot-check (token-exact or the numbers are void)
    cfg, model, params, mgr, be, eng = _node("mamba2-2.7b", seed=3,
                                             n_slots=4)
    rng = np.random.default_rng(3)
    turns = [list(map(int, rng.integers(0, cfg.vocab, n))) for n in (11, 9)]
    want = dense_reference(cfg, model, params, {"p0": turns}, gen)["p0"]
    got, now = [], 0.0
    for t in turns:
        req = InferenceRequest(session_id="p0", prompt_tokens=len(t),
                               max_new_tokens=gen, prompt_ids=list(t),
                               cached_tokens=be.session_tokens("p0"))
        eng.submit(req)
        while eng.waiting or eng.running:
            now += eng.step(now)
        got.append(req.output_ids)
        be.swap_out("p0", be.session_tokens("p0"))
        be.drain_transfers()
    parity_ok = got == want

    # sessions/node headroom at FULL scale, equal hardware: analytic HBM
    # budget over per-session state bytes at headroom_tokens of context
    headroom = {}
    for arch in ("llama3-8b", "mamba2-2.7b"):
        cost = CostModel(get_config(arch), HardwareSpec())
        per = cost.session_kv_bytes(headroom_tokens)
        headroom[arch] = dict(
            session_bytes=per,
            sessions_per_node=cost.hbm_kv_budget() / per)

    out = dict(
        ctx_len=ctx_len, gen=gen, kernel_mode=kernel_mode,
        headroom_tokens=headroom_tokens,
        kv=kv, recurrent=rec,
        swap_bytes_ratio=kv["resident_bytes"] / rec["resident_bytes"],
        # O(1) state: resident bytes must not depend on context length
        state_bytes_flat=(rec["resident_bytes"]
                          == rec["resident_bytes_half_ctx"]),
        headroom=headroom,
        headroom_ratio=(headroom["mamba2-2.7b"]["sessions_per_node"]
                        / headroom["llama3-8b"]["sessions_per_node"]),
        parity_ok=bool(parity_ok),
    )
    emit("recurrent.swap_bytes_ratio", out["swap_bytes_ratio"],
         f"kv={kv['resident_bytes']}B state={rec['resident_bytes']}B "
         f"at ctx={ctx_len} flat={out['state_bytes_flat']} "
         f"parity_ok={parity_ok}")
    emit("recurrent.stall_cold_ms", rec["stall_cold_ms"],
         f"kv_cold={kv['stall_cold_ms']:.2f}ms "
         f"headroom_ratio={out['headroom_ratio']:.1f}x "
         f"at {headroom_tokens} tok/session")
    save("BENCH_recurrent", out)
    return out


def bench_quant(n_sessions: int = 10, kernel_mode: str = None):
    """Quantized-in-HBM-tier mode: the capacity-vs-fidelity observables.

    Four sections land in ``BENCH_quant.json``:

    * ``parity`` — kernel-level max-abs-error of the mixed-precision
      attention path: quant-Pallas(interpret) vs the jnp quant oracle
      (must be ~exact) and quant vs fp (the bounded int8 loss);
    * ``headroom`` — the headline: ``n_sessions`` idle-but-warm sessions
      stream through a node whose fp byte budget (``hbm_pages``) is half
      its physical page slots, each advising imminent reuse.  With the
      tier ON, admission pressure compresses idle sessions to int8 in
      place and the peak count of fully-HBM-resident sessions must reach
      >= 1.7x the fp-only baseline (same byte budget, no quantize);
    * compile discipline — the compress dispatch is bucketed like every
      other paged dispatch: after the first pressure round, later
      sessions must add ZERO compiles (``steady_compiles``);
    * ``sim_ab`` — cluster-sim eviction-policy A/B on the ShareGPT trace:
      quantize-before-swap must cut tier-transfer bytes at equal-or-
      better TBT (sim sessions are repriced through the same CostModel
      compress costs the real backend pays)."""
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.advisory import AdvisoryRequest, InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.kernels import ops
    from repro.kernels.quant import quantize_int8
    from repro.models.registry import get_model
    from repro.serving.backend import RealBackend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine

    if kernel_mode is None:
        kernel_mode = "auto" if jax.default_backend() == "tpu" else "ref"
    cfg = get_config("llama3-8b").reduced(dtype="float32", n_kv_heads=2)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))

    # -- kernel parity ------------------------------------------------------
    rng = np.random.default_rng(0)
    Hkv, H, D, P, page, B, Sq, maxp = 2, 4, 16, 8, 8, 2, 8, 3
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, Hkv, page, D)), jnp.float32)
    tab = jnp.asarray(rng.integers(0, P, (B, maxp)), jnp.int32)
    qo = jnp.asarray([5, 16], jnp.int32)
    ctx = qo + Sq
    kq, ks = quantize_int8(kp, axis=(1, 2, 3))
    vq, vs = quantize_int8(vp, axis=(1, 2, 3))
    flags = jnp.asarray(rng.integers(0, 2, (P,)), jnp.int32)
    quant = (kq, vq, ks, vs, flags)
    o_ref_q = ops.paged_chunk_attention(q, kp, vp, tab, qo, ctx,
                                        mode="ref", quant=quant)
    o_int_q = ops.paged_chunk_attention(q, kp, vp, tab, qo, ctx,
                                        mode="interpret", quant=quant)
    o_fp = ops.paged_chunk_attention(q, kp, vp, tab, qo, ctx, mode="ref")
    parity = dict(
        pallas_vs_oracle=float(jnp.max(jnp.abs(o_int_q - o_ref_q))),
        quant_vs_fp=float(jnp.max(jnp.abs(o_ref_q - o_fp))))

    # -- measured headroom: quant tier on vs off, same fp byte budget -------
    HBM_PAGES, PAGE = 16, 8

    def _cohort(quantize: bool):
        cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
        cost.set_param_count(model.param_count())
        mgr = NodeManager(0, cfg, cost, enable_quantize=quantize)
        be = RealBackend(cfg, model, params, mgr=mgr, page_size=PAGE,
                         n_pages=3 * HBM_PAGES if quantize else HBM_PAGES,
                         hbm_pages=HBM_PAGES, trace_logits=False,
                         kernel_mode=kernel_mode)
        eng = NodeEngine(0, cfg, cost, mgr, max_batch=4, backend=be)
        rng = np.random.default_rng(1)
        now, peak, compiles = 0.0, 0, []

        def resident():
            return sum(1 for e in mgr.store.entries.values()
                       if all(t == "hbm" for t in e.tier))

        if quantize:
            # warm the quant one-off buckets outside the measured census:
            # the compress dispatch (first quantize), the in-place
            # dequantizing fork (quantize->swap demotion), and the
            # dequantizing gather a quantized session pays on its way to
            # the host tier.  All are shape-bucketed, so one warm-up round
            # trip covers every later session of this cohort and the
            # steady-state gate sees only per-session cost.
            p = list(map(int, rng.integers(0, cfg.vocab, 21)))
            eng.submit(InferenceRequest(session_id="warm",
                                        prompt_tokens=21, max_new_tokens=6,
                                        prompt_ids=p))
            while eng.waiting or eng.running:
                now += eng.step(now)
            be.quantize_session("warm")
            be._dequantize_session("warm")   # in-place fork bucket
            be.quantize_session("warm")
            be.swap_out("warm", be.session_tokens("warm"))
            be.drain_transfers()

        for i in range(n_sessions):
            p = list(map(int, rng.integers(0, cfg.vocab, 21)))
            eng.submit(InferenceRequest(session_id=f"s{i}",
                                        prompt_tokens=21, max_new_tokens=6,
                                        prompt_ids=p))
            census = dict(be.compile_counts())
            while eng.waiting or eng.running:
                now += eng.step(now)
                peak = max(peak, resident())
            compiles.append(sum(be.compile_counts().values())
                            - sum(census.values()))
            # the advisory that makes this session "warm": predicted reuse
            # is imminent, so pressure should compress it, not evict it
            mgr.on_advisory(AdvisoryRequest(session_id=f"s{i}",
                                            expected_arrival=0.05),
                            kv_node=0, now=now)
        return dict(
            peak_resident_sessions=peak,
            final_resident_sessions=resident(),
            quantized_sessions=mgr.stats["quantized_sessions"],
            quantize_freed_bytes=mgr.stats["quantize_freed_bytes"],
            evictions=mgr.stats["evictions"],
            quant_dispatches=be.stats["quant_dispatches"],
            quantized_pages=be.stats["quantized_pages"],
            # per-session compile deltas: the quant one-offs (compress,
            # dequantizing gather) are warmed before the census, so after
            # the serving buckets warm on the early sessions the tail must
            # be ZERO (every compress/fork dispatch is padded to the same
            # bucket)
            compiles_per_session=compiles,
            steady_compiles=sum(compiles[-3:]),
            compile_counts=dict(be.compile_counts()),
        )

    quant_arm = _cohort(quantize=True)
    fp_arm = _cohort(quantize=False)
    headroom = (quant_arm["peak_resident_sessions"]
                / max(fp_arm["peak_resident_sessions"], 1))

    # -- sim eviction-policy A/B -------------------------------------------
    def _sim_arm(quantize: bool):
        from repro.serving.simulator import ClusterRuntime
        from repro.traces.sharegpt import ShareGPTTrace
        # paper-testbed hosts with the HBM shaved down so ~20 resident
        # sessions/node saturate the KV budget — the memory-pressure regime
        # the quantize-vs-swap policy exists for
        ab_hw = HardwareSpec(chips_per_replica=2, hbm_bytes=10e9,
                             host_dram=128e9)
        sim = ClusterRuntime(get_config("llama3-8b"), n_nodes=2,
                             policy="symphony", hw=ab_hw, max_batch=32)
        for m in sim.managers.values():
            m.enable_quantize = quantize
        try:
            res = sim.run(ShareGPTTrace(n_users=96, n_sessions=192, seed=0))
            mgrs = list(sim.managers.values())
            return dict(
                completed=len(res.completed),
                tpot_mean_s=res.mean("tpot"),
                ttft_mean_s=res.mean("ttft"),
                throughput_rps=res.throughput,
                evicted_bytes=sum(m.stats["evicted_bytes"] for m in mgrs),
                migrated_bytes=sum(m.stats["migrated_bytes"] for m in mgrs),
                evictions=sum(m.stats["evictions"] for m in mgrs),
                quantized_sessions=sum(m.stats["quantized_sessions"]
                                       for m in mgrs),
            )
        finally:
            sim.cleanup()

    ab_on, ab_off = _sim_arm(True), _sim_arm(False)
    sim_ab = dict(
        quantize_on=ab_on, quantize_off=ab_off,
        transfer_bytes_ratio=(ab_on["evicted_bytes"]
                              / max(ab_off["evicted_bytes"], 1.0)),
        tpot_ratio=(ab_on["tpot_mean_s"]
                    / max(ab_off["tpot_mean_s"], 1e-12)),
    )

    out = dict(
        n_sessions=n_sessions, hbm_pages=HBM_PAGES, page_size=PAGE,
        kernel_mode=kernel_mode,
        parity=parity,
        headroom=dict(quant=quant_arm, fp=fp_arm, ratio=headroom),
        sim_ab=sim_ab,
        compile_counts=dict(model.paged_compile_counts()),
    )
    emit("quant.headroom.ratio", headroom,
         f"quant_peak={quant_arm['peak_resident_sessions']} "
         f"fp_peak={fp_arm['peak_resident_sessions']} "
         f"steady_compiles={quant_arm['steady_compiles']} "
         f"parity_fp={parity['quant_vs_fp']:.4f}")
    emit("quant.sim_ab.transfer_bytes_ratio",
         sim_ab["transfer_bytes_ratio"],
         f"tpot_ratio={sim_ab['tpot_ratio']:.3f} "
         f"quantized_sessions={ab_on['quantized_sessions']} "
         f"evictions {ab_off['evictions']}->{ab_on['evictions']}")
    save("BENCH_quant", out)
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--step", action="store_true",
                    help="emit the BENCH_step.json serving-step artifact "
                         "(includes the long-prompt interference mode)")
    ap.add_argument("--interference-only", action="store_true",
                    help="run just the long-prompt interference mode")
    ap.add_argument("--overlap-only", action="store_true",
                    help="run just the async swap-in overlap mode")
    ap.add_argument("--hetero-only", action="store_true",
                    help="run just the heterogeneous-skew mode: 1 long + "
                         "15 short decode lanes vs a context-matched "
                         "homogeneous baseline, with the DMA'd-pages-per-"
                         "step counter (emits the BENCH_hetero.json "
                         "artifact)")
    ap.add_argument("--sharing-only", action="store_true",
                    help="run just the 1000-session prefix-sharing mode "
                         "(emits the BENCH_sharing.json artifact)")
    ap.add_argument("--recurrent-only", action="store_true",
                    help="run just the recurrent-state mode: O(1) slot-blob "
                         "swap vs linear paged-KV swap + sessions/node "
                         "headroom (emits the BENCH_recurrent.json artifact)")
    ap.add_argument("--quant-only", action="store_true",
                    help="run just the quantized-KV-tier mode: in-place "
                         "int8 headroom vs fp baseline, kernel parity, and "
                         "the sim quantize-vs-swap A/B (emits the "
                         "BENCH_quant.json artifact)")
    ap.add_argument("--mesh-only", action="store_true",
                    help="run just the tensor-parallel serving mode (emits "
                         "the BENCH_mesh.json artifact; needs --tp visible "
                         "devices — force host devices via XLA_FLAGS on CPU)")
    ap.add_argument("--tp", type=int, default=2,
                    help="mesh size for --mesh-only")
    ap.add_argument("--prompt-len", type=int, default=4000)
    ap.add_argument("--token-budget", type=int, default=4)
    ap.add_argument("--sessions", type=int, default=1000)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.interference_only:
        import json
        print(json.dumps(bench_interference(args.prompt_len,
                                            args.token_budget), indent=1))
    elif args.overlap_only:
        import json
        print(json.dumps(bench_overlap(), indent=1))
    elif args.hetero_only:
        import json
        print(json.dumps(bench_hetero(), indent=1))
    elif args.sharing_only:
        import json
        print(json.dumps(bench_sharing(n_sessions=args.sessions), indent=1))
    elif args.recurrent_only:
        import json
        print(json.dumps(bench_recurrent(), indent=1))
    elif args.quant_only:
        import json
        print(json.dumps(bench_quant(), indent=1))
    elif args.mesh_only:
        import json
        print(json.dumps(bench_mesh(tp=args.tp), indent=1))
    elif args.step:
        bench_step()
    else:
        bench_kernels()
