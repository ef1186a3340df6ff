"""One run of a serving cell: set-up, the measured window, the check.

The path the window drives is the program's own: `NodeEngine.step` over a
`RealBackend` from `make_backend`, under a `NodeManager`, with each
turn's advisory handed to `NodeManager.on_advisory` at its scheduled time.
Arrivals are open loop on the host clock; a token's time is the host
clock after the step that fetched it, and a request's latency runs from
its scheduled arrival.

`run` takes a configuration file, a mix and a seed and returns everything
the result line needs; it never looks for a chip itself, so the tests
drive it on the CPU at small sizes (`bench/run.py` is the chip's entry).
"""
from __future__ import annotations

import gc
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import tails
from bench.model import load_arch, make_params
from bench.traffic import Traffic, generate


def _pages(tokens: int, page: int) -> int:
    return -(-tokens // page)


@dataclass(eq=False)
class Record:
    """What the harness saw of one request."""
    req: object
    sid: str
    at: float                      # scheduled arrival
    prefix: List[int]              # context the turn was sent with
    admitted: Optional[float] = None
    n: int = 0                     # tokens seen so far
    first: Optional[float] = None
    last: Optional[float] = None
    done: Optional[float] = None
    host: bool = False             # some of its KV was off the device
    int8: bool = False             # its first step read int8 pages


@dataclass
class Window:
    """Everything measured in the window, for the metrics and readers."""
    seconds: float
    records: List[Record] = field(default_factory=list)
    gaps: List[float] = field(default_factory=list)
    tokens: int = 0
    steps: int = 0
    step_s: float = 0.0
    step_flops: int = 0
    attn_steps: List[tuple] = field(default_factory=list)
    stall_s: float = 0.0
    copied_bytes: float = 0.0
    stats: Dict[str, float] = field(default_factory=dict)
    late_s: float = 0.0            # how far behind schedule submits ran
    queue: List[tuple] = field(default_factory=list)  # (t, waiting)


class Cell:
    """The program under test, set up for one configuration and mix."""

    def __init__(self, conf: dict, mix: dict, seed: int, seconds: float,
                 kernel_mode: str = "auto", model=None):
        import jax

        from repro.core.node_manager import NodeManager
        from repro.models.registry import get_model
        from repro.serving.backend import make_backend
        from repro.serving.cost_model import CostModel, HardwareSpec
        from repro.serving.engine import NodeEngine

        self.conf, self.mix, self.seed = conf, mix, seed
        srv = conf["serving"]
        self.arch = arch = load_arch(conf)
        self.cfg = cfg = arch.model_config(conf)
        self.page = srv["page_size"]
        t0 = time.perf_counter()
        self.params = make_params(arch.param_shapes(cfg), cfg.dtype, seed)
        jax.block_until_ready(self.params)
        self.params_s = time.perf_counter() - t0
        model = get_model(cfg) if model is None else model
        hbm_pages = srv["hbm_pages"]
        budget = hbm_pages * self.page * arch.kv_token_bytes(cfg)
        # the store's HBM budget is the pool's: CostModel reserves 10%
        # and the weights, so give it the chip size that leaves exactly
        # the backend's fp budget
        n_params = model.param_count()
        hw = HardwareSpec(chips_per_replica=1,
                          hbm_bytes=budget / 0.9 + 2 * n_params)
        cost = CostModel(cfg, hw)
        cost.set_param_count(n_params)
        self.mgr = NodeManager(0, cfg, cost,
                               enable_quantize=srv["int8_tier"])
        self.be = make_backend(cfg, model, self.params,
                               n_pages=srv["n_pages"], hbm_pages=hbm_pages,
                               page_size=self.page, mgr=self.mgr,
                               trace_logits=False, kernel_mode=kernel_mode)
        self.eng = NodeEngine(0, cfg, cost, self.mgr,
                              max_batch=srv["max_batch"],
                              backend=self.be,
                              token_budget=srv["token_budget"])
        self.traffic: Traffic = generate(
            mix, seed, hbm_pages * self.page, seconds, cfg.vocab)
        self.ids: Dict[str, List[int]] = {}

    # -- set-up ----------------------------------------------------------

    def lattice(self) -> List[tuple]:
        """Every (lanes, tokens, table width) bucket the window can
        dispatch, on the backend's own power-of-two lattice: any lane
        count up to ``max_batch``, any chunk up to the token budget plus
        the pending token a returning turn carries, and tables from the
        shortest history's to the mix's context cap."""
        from repro.serving.backend import _bucket
        srv, mix = self.conf["serving"], self.mix

        def upto(lo, hi):
            return [b for b in (1 << k for k in range(32))
                    if _bucket(lo) <= b <= _bucket(hi)]
        lanes = upto(1, srv["max_batch"])
        toks = upto(1, srv["token_budget"] + 1)
        widths = upto(_pages(mix["history"]["min"] + 1, self.page),
                      _pages(mix["max_context"], self.page))
        return [(b, t, w) for b in lanes for t in toks for w in widths]

    def warm(self, threads: int = 8) -> int:
        """Compile every step program the window can run: lowered one at
        a time with the arguments the backend dispatches (the jit caches
        then hand the dispatch the same executables), compiled on
        ``threads`` threads.  Returns the number of programs warmed."""
        import jax.numpy as jnp
        be, cfg = self.be, self.cfg
        L, P = cfg.n_layers, be.n_pages

        def step_args(Bb, Sq, Tb):
            z = jnp.zeros((Bb,), jnp.int32)
            return (be.params, np.zeros((Bb, Sq), np.int32), be.k_pool,
                    be.v_pool, np.zeros((L, Bb, Tb), np.int32), z, z, z,
                    np.full((L, Bb, Sq), P, np.int32),
                    np.zeros((L, Bb, Sq), np.int32), be._quant_args())

        lattice = self.lattice()
        t0 = time.perf_counter()
        lowered = [be.model.lower_step_paged(*step_args(*b),
                                             kernel_mode=be.kernel_mode)
                   for b in lattice]
        t1 = time.perf_counter()
        with ThreadPoolExecutor(threads) as ex:
            list(ex.map(lambda lw: lw.compile(), lowered))
        del lowered
        t2 = time.perf_counter()
        # the step's token fetch slices the padded ids to the live lanes
        for Bb in sorted({b for b, _, _ in lattice}):
            for B in range(1, Bb + 1):
                np.asarray(jnp.zeros((Bb,), jnp.int32)[:B])
        self.warm_s = dict(lower=t1 - t0, compile=t2 - t1,
                           others=time.perf_counter() - t2)
        return len(lattice)

    def fill(self) -> None:
        """Build every session's history through the normal path, one
        session at a time: each is a first turn of its whole history that
        emits one token, which stays pending into the session's next
        turn.  The engine evicts older sessions to the host as the pool
        fills, as it would in service."""
        from repro.core.advisory import InferenceRequest
        for sid, hist in self.traffic.histories.items():
            req = InferenceRequest(
                session_id=sid, prompt_tokens=len(hist), max_new_tokens=1,
                prompt_ids=list(hist))
            self.eng.submit(req)
            while self.eng.waiting or self.eng.running:
                self.eng.step(0.0)
            self.ids[sid] = list(hist) + list(req.output_ids)
        self.be.drain_transfers()
        self.eng.completed.clear()

    # -- the window ------------------------------------------------------

    def window(self, seconds: float, annotate=None) -> Window:
        """Serve the mix's turns open loop for ``seconds`` of host clock."""
        from repro.core.advisory import AdvisoryRequest, InferenceRequest
        span = annotate or _null_span
        eng, be, mgr, cfg = self.eng, self.be, self.mgr, self.cfg
        arch = self.arch
        turns = self.traffic.turns
        advs = sorted(range(len(turns)), key=lambda i: turns[i].advise_at)
        w = Window(seconds=seconds)
        inflight: Dict[int, Record] = {}
        busy: Dict[str, Record] = {}
        deferred: Dict[str, list] = {}
        kv_bytes = 2 + (1 if be._quant_active else 0)
        stats0 = dict(be.stats)
        stall0 = eng.stats["stall_s"]
        ti = ai = 0
        off_device: Dict[str, bool] = {}   # KV not all in HBM at advice

        def off(sid):
            e = mgr.store.entries.get(sid)
            return e is not None and any(t != "hbm" for t in e.tier)

        def advise(tr, now):
            off_device[tr.sid] = off_device.get(tr.sid) or off(tr.sid)
            mgr.on_advisory(AdvisoryRequest(
                session_id=tr.sid, expected_arrival=tr.lead,
                issued_at=now), 0, now)

        # advisories due before the window were issued while set-up ran,
        # as they would have been in steady service
        while ai < len(advs) and turns[advs[ai]].advise_at <= 0.0:
            advise(turns[advs[ai]], turns[advs[ai]].advise_at)
            ai += 1
        clock = time.perf_counter
        t0 = clock()

        def submit(i, now):
            tr = turns[i]
            ctx = self.ids[tr.sid]
            req = InferenceRequest(
                session_id=tr.sid, prompt_tokens=len(tr.new_ids),
                max_new_tokens=tr.max_new, arrival=tr.at,
                prompt_ids=list(tr.new_ids),
                cached_tokens=be.session_tokens(tr.sid))
            rec = Record(req=req, sid=tr.sid, at=tr.at,
                         prefix=ctx + list(tr.new_ids),
                         host=bool(off_device.pop(tr.sid, False)
                                   or off(tr.sid)))
            w.records.append(rec)
            inflight[id(req)] = rec
            busy[tr.sid] = rec
            w.late_s = max(w.late_s, now - tr.at)
            eng.submit(req)

        while True:
            now = clock() - t0
            if now >= seconds:
                break
            with span("bench.advisory"):
                while ai < len(advs) and turns[advs[ai]].advise_at <= now:
                    advise(turns[advs[ai]], now)
                    ai += 1
            with span("bench.submit"):
                while ti < len(turns) and turns[ti].at <= now:
                    if turns[ti].sid in busy:     # reply still streaming
                        deferred.setdefault(turns[ti].sid, []).append(ti)
                    else:
                        submit(ti, now)
                    ti += 1
            if not (eng.waiting or eng.running):
                nxt = min(turns[ti].at if ti < len(turns) else seconds,
                          turns[advs[ai]].advise_at if ai < len(advs)
                          else seconds, seconds)
                with span("bench.wait"):
                    time.sleep(max(0.0, nxt - (clock() - t0)))
                continue
            waiting = {id(r) for r in eng.waiting}
            active = [r.sid for r in inflight.values()]
            before = {s: be.seqs[s].n_kv for s in active if s in be.seqs}
            t_step = clock() - t0
            with span("bench.step"):
                eng.step(t_step)
            t = clock() - t0
            w.steps += 1
            w.step_s += t - t_step
            w.queue.append((t, len(eng.waiting)
                            + sum(len(q) for q in deferred.values())))
            lanes = []
            waiting = {id(r) for r in eng.waiting}
            for key, rec in list(inflight.items()):
                req = rec.req
                if rec.admitted is None and key not in waiting:
                    rec.admitted = t_step
                    rec.int8 = any(a.quantized_pages_of(rec.sid)
                                   for a in be.alloc)
                st = be.seqs.get(rec.sid)
                n = len(req.output_ids or ())
                if st is not None and st.n_kv != before.get(rec.sid,
                                                            st.n_kv):
                    q_off = before[rec.sid]
                    lanes.append((q_off, st.n_kv - q_off, n > rec.n))
                if n > rec.n:
                    w.tokens += n - rec.n
                    if rec.first is None:
                        rec.first = t
                    else:
                        w.gaps.append(t - rec.last)
                    rec.last, rec.n = t, n
                if req.finished_at is not None:
                    rec.done = t
                    del inflight[key]
                    busy.pop(rec.sid, None)
                    self.ids[rec.sid] = rec.prefix + list(req.output_ids)
                    q = deferred.get(rec.sid)
                    if q:
                        submit(q.pop(0), t)
            w.step_flops += arch.step_flops(cfg, lanes)
            if lanes:
                w.attn_steps.append(
                    arch.step_attn(cfg, lanes, self.page, kv_bytes))
        # turns due before the close whose session was still busy: they
        # arrived, so they count in the tail at their wait so far
        for i in (i for q in deferred.values() for i in q):
            w.records.append(Record(req=None, sid=turns[i].sid,
                                    at=turns[i].at, prefix=[]))
        w.stall_s = eng.stats["stall_s"] - stall0
        w.copied_bytes = be.stats["copied_bytes"] - stats0["copied_bytes"]
        w.stats = {k: be.stats[k] - stats0[k] for k in be.stats}
        return w

    def free(self) -> None:
        """Drop the program's state (pools, engine, manager)."""
        self.be.drain_transfers()
        del self.eng, self.be, self.mgr
        gc.collect()


class _null_span:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# -- correctness ---------------------------------------------------------

def sample(records: List[Record], seed: int, tokens: int,
           max_requests: int) -> List[Record]:
    """Finished requests drawn from the seed: the one with the longest
    context, one whose KV came back from the host and one whose first
    step read int8 pages (where the window has them), then others until
    ``tokens`` served tokens."""
    done = [r for r in records if r.done is not None]
    if not done:
        return []
    rng = np.random.default_rng((int(seed) % (1 << 62)) + 1)
    order = [done[i] for i in rng.permutation(len(done))]
    out = [max(done, key=lambda r: len(r.prefix) + r.n)]
    for want in ("host", "int8"):
        if not any(getattr(r, want) for r in out):
            pick = next((r for r in order if getattr(r, want)), None)
            if pick is not None:
                out.append(pick)
    for r in order:
        if sum(x.n for x in out) >= tokens or len(out) >= max_requests:
            break
        if all(r is not x for x in out):
            out.append(r)
    return out


def gaps(conf: dict, params, rec: Record, fp8: bool = False) -> np.ndarray:
    """Per served token: how far below the reference's best logit the
    token lies, in standard deviations of the reference's logits at that
    position.  The reference is the architecture module's (`load_arch`).
    With ``fp8`` the token is the one the fp8 control puts first, read
    against the float32 reference."""
    out = list(rec.req.output_ids)
    seq = rec.prefix + out[:-1]
    at = np.arange(len(rec.prefix) - 1, len(seq))
    arch = load_arch(conf)
    ref = arch.logits(conf, params, seq, at)
    toks = np.asarray(out)
    if fp8:
        toks = arch.logits(conf, params, seq, at, fp8=True).argmax(-1)
    best = ref.max(-1)
    return (best - ref[np.arange(len(at)), toks]) / ref.std(-1)


# -- one run ---------------------------------------------------------------

def run(conf: dict, mix: dict, seed: int, seconds: float, *,
        trace_dir: Optional[str] = None, t_start: Optional[float] = None,
        kernel_mode: str = "auto", compiles: Optional[dict] = None,
        fault=None) -> dict:
    """Set up, measure, check.  Returns the result's parts: ``correct``,
    ``attempted``, ``failed``, ``end_to_end``, ``window``, ``compared``
    and the set-up seconds.  ``compiles`` is a counter box the caller
    keeps current (``{"n": ...}``).  ``fault`` (tests only) is called with
    the set-up cell just before the window, to break the timed path."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    compiles = compiles if compiles is not None else {"n": 0}
    cell = Cell(conf, mix, seed, seconds, kernel_mode=kernel_mode)
    warmed = cell.warm()
    t_fill = time.perf_counter()
    cell.fill()
    fill_s = time.perf_counter() - t_fill
    if fault is not None:
        fault(cell)
    setup_s = time.perf_counter() - t_start
    annotate = None
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no Python call events
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    n0, s0 = compiles["n"], compiles.get("s", 0.0)
    jax.config.update("jax_log_compiles", True)   # name what compiles
    t_w = time.perf_counter()
    win = cell.window(seconds, annotate=annotate)
    jax.effects_barrier()
    wall = time.perf_counter() - t_w
    jax.config.update("jax_log_compiles", False)
    window_compiles = dict(n=compiles["n"] - n0,
                           s=compiles.get("s", 0.0) - s0)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    chk = mix["check"]
    picked = sample(win.records, seed, chk["sample_tokens"],
                    chk["max_requests"])
    cfg = cell.cfg
    history = cell.traffic.history_tokens
    sessions = len(cell.traffic.histories)
    params = cell.params
    cell.free()
    t_chk = time.perf_counter()
    worst, n_cmp = float("inf"), 0
    if picked:
        worst = 0.0
        for rec in picked:
            g = gaps(conf, params, rec)
            worst = max(worst, float(g.max()))
            n_cmp += len(g)
    compared = [dict(name="max_logit_gap_over_std", value=worst,
                     limit=chk["max_gap_over_std"], rule="<="),
                dict(name="tokens_compared", value=n_cmp,
                     limit=chk["min_tokens_compared"], rule=">=")]
    correct = all(c["value"] <= c["limit"] if c["rule"] == "<="
                  else c["value"] >= c["limit"] for c in compared)
    check_s = time.perf_counter() - t_chk
    ttft = tails.ttft(win.records, seconds)
    return dict(
        correct=bool(correct), attempted=len(win.records), failed=0,
        setup_s=setup_s, fill_s=fill_s, warmed=warmed, wall_s=wall,
        window=win, window_compiles=window_compiles, peak_bytes=peak,
        warm_s=cell.warm_s, params_s=cell.params_s, check_s=check_s,
        compared=compared, picked=len(picked), cfg=cfg,
        picked_kinds=dict(host=sum(r.host for r in picked),
                          int8=sum(r.int8 for r in picked),
                          tokens=n_cmp),
        history_tokens=history, sessions=sessions,
        ttft_p95_ms=1e3 * tails.p95(ttft),
        end_to_end=dict(
            ttft_p50_ms=1e3 * tails.percentile(ttft, 50),
            tbt_p95_ms=1e3 * tails.p95(win.gaps) if win.gaps else None,
            tokens_per_s=win.tokens / seconds,
            setup_s=setup_s))
