#!/usr/bin/env python3
"""Chip smoke: serve codeqwen1.5-7b widths on a TPU through the normal path.

The serving path a user drives — `NodeManager`, `make_backend` (a
`RealBackend`), `NodeEngine.step`, `DenseLM.step_paged` and the compiled
Pallas `paged_chunk_attention` — serves four multi-turn sessions at the
published widths of codeqwen1.5-7b (d_model 4096, 32 heads, 32 KV heads,
d_head 128, d_ff 13440, vocab 92416), bf16, with random weights from
``--seed``.  Depth is cut from 32 to 4 layers: the whole model is 16.4 GB
of bf16 and would leave one 16 GB chip no room for KV.

Turn 1 prefills prompts of 1024 or 1536 tokens in chunks of the engine's
token budget (512) and generates 16 tokens.  Between turns one session is
swapped to host memory (it comes back at its next admission) and one is
quantized to the int8 tier in place, so the quantized kernel variant
serves its turn 2.  Turn 2 is a 511-token continuation prefill against the
cached pages plus 16 new tokens.  Prompt and turn lengths sit on the
512-token chunk lattice so that the run compiles a handful of step
programs.

Checks, on the chip:
  * the backend resolved its kernel mode to compiled Pallas;
  * the served logits at the first generated position of each turn of
    every full-precision session agree with the float32 reference
    (`repro.models.reference`) within the tolerances below;
  * the quantized session emits exactly the tokens of a twin run in which
    that session's quantized pages were re-inflated by a host round trip
    (both read the same dequantized values);
  * the swap and quantize round trips ran.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # tp=4 on one 4-chip host vs one chip

With ``--chips 4`` only the tensor-parallel path runs: the same sessions on
a ``("model",)`` mesh of 4 chips (32 KV heads, 8 per chip, the attention
kernel under `jax.shard_map`) and, in the same process, on one chip; their
logits are compared with each other and with the reference.

The last line of stdout is ``{"ok": true, "device": {...}}``.  The script
exits non-zero and prints no result when JAX finds no TPU or any check
fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

GEN = 16                 # new tokens per turn
TOKEN_BUDGET = 512       # the engine's default chunk budget
TURN2_LEN = TOKEN_BUDGET - 1   # pending token + 511 = one full chunk
PAGE = 16
N_LAYERS = 4             # depth cut from the published 32
POOL_BYTES_PER_SIDE = 1 << 30
SWAP, QUANT = "s1", "s2"     # the sessions acted on between turns

# Logit tolerances against the float32 reference.  The served path keeps
# activations, residual stream and KV in bf16 (unit roundoff 2^-8 = 3.9e-3)
# and rounds after every matmul of the 4 layers' 8 residual updates and
# before the 4096-wide head contraction; those roundings compound to about
# 1-1.5% relative error in the logits (measured at smaller widths on the
# CPU), so:
#   REL_RMS  ||served - ref||_2 / ||ref||_2 <= 0.04: about 3x that, and
#            below what fp8 activations (roundoff 2^-4) would give;
#   MAX_ABS  max |served - ref| <= 0.2 * std(ref): the largest of 92,416
#            errors of RMS 0.04 * std(ref) is about 4.5 of their sigmas.
REL_RMS = 0.04
MAX_ABS_OVER_STD = 0.2
KERNEL_MODE = "pallas"   # what the backend must resolve "auto" to


def expect(cond, what):
    if not cond:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        sys.exit(2)
    return devs


def model_config():
    from repro.configs import get_config
    pub = get_config("codeqwen1.5-7b")
    cfg = dataclasses.replace(pub, n_layers=N_LAYERS)
    print(f"config: {pub.name} d_model={cfg.d_model} heads={cfg.n_heads} "
          f"kv_heads={cfg.n_kv_heads} d_head={cfg.d_head} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} dtype={cfg.dtype}; cut: n_layers "
          f"{pub.n_layers} -> {cfg.n_layers} (the whole model is "
          f"{pub.param_count() * 2 / 1e9:.1f} GB of bf16)", flush=True)
    return cfg


def make_sessions(cfg, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    sessions = {}
    for i in range(4):
        n1 = TOKEN_BUDGET * int(rng.integers(2, 4))       # 1024 or 1536
        ids = lambda n: [int(t) for t in rng.integers(0, cfg.vocab, n)]
        sessions[f"s{i}"] = (ids(n1), ids(TURN2_LEN))
    return sessions


def serve(cfg, model, params, sessions, *, mesh=None, twin=False):
    """Serve both turns of every session through the normal path.

    ``twin``: after quantizing the QUANT session, also swap it to host and
    back, which re-inflates its int8 pages to the pool dtype."""
    import numpy as np

    from repro.core.advisory import InferenceRequest
    from repro.core.node_manager import NodeManager
    from repro.serving.backend import make_backend
    from repro.serving.cost_model import CostModel, HardwareSpec
    from repro.serving.engine import NodeEngine
    from repro.serving.transfer import OUT

    page_bytes = (cfg.n_layers * cfg.n_kv_heads * PAGE * cfg.d_head
                  * np.dtype(cfg.dtype).itemsize)
    n_pages = POOL_BYTES_PER_SIDE // page_bytes
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    be = make_backend(cfg, model, params, n_pages=n_pages, page_size=PAGE,
                      mgr=mgr, mesh=mesh)
    expect(be.kernel_mode == KERNEL_MODE,
           f"kernel mode {be.kernel_mode!r}, want {KERNEL_MODE!r}")
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=len(sessions),
                     backend=be, token_budget=TOKEN_BUDGET)
    out = dict(kernel_mode=be.kernel_mode, n_pages=n_pages, logits={},
               history={}, tokens={}, compile_s=0.0, steps=0)
    hist = {sid: [] for sid in sessions}
    now = 0.0
    t_run = time.perf_counter()
    for turn in (0, 1):
        reqs = {}
        for sid, prompts in sessions.items():
            reqs[sid] = InferenceRequest(
                session_id=sid, prompt_tokens=len(prompts[turn]),
                max_new_tokens=GEN, prompt_ids=list(prompts[turn]),
                cached_tokens=be.session_tokens(sid))
            hist[sid] = hist[sid] + list(prompts[turn])
            eng.submit(reqs[sid])
        first = len(be.logit_trace)
        while eng.waiting or eng.running:
            census = be.compile_counts()
            t0 = time.perf_counter()
            now += eng.step(now)
            dt = time.perf_counter() - t0
            out["steps"] += 1
            if be.compile_counts() != census:
                out["compile_s"] += dt
        for sid, r in reqs.items():
            expect(len(r.output_ids) == GEN,
                   f"{sid} turn {turn + 1} emitted {r.output_ids}")
            lg = next(l for s, l in be.logit_trace[first:] if s == sid)
            out["logits"][sid, turn] = lg
            out["history"][sid, turn] = list(hist[sid])
            out["tokens"][sid, turn] = list(r.output_ids)
            hist[sid] = hist[sid] + list(r.output_ids)
        if turn == 0:
            be.swap_out(SWAP, be.session_tokens(SWAP))
            be.drain_transfers(OUT)
            expect(be.quantize_session(QUANT) > 0,
                   f"{QUANT}: nothing quantized")
            if twin:
                be.swap_out(QUANT, be.session_tokens(QUANT))
                be.drain_transfers(OUT)
        elif not twin:
            expect(all(a.quantized_pages_of(QUANT) for a in be.alloc),
                   f"{QUANT} served turn 2 without its int8 pages")
    out["serve_s"] = time.perf_counter() - t_run
    st = be.stats
    expect(st["swaps_out"] >= (2 if twin else 1)
           and st["swaps_in"] >= st["swaps_out"],
           f"host round trips incomplete: {st}")
    expect(st["quantized_pages"] > 0 and st["quant_dispatches"] >= 1,
           f"quantize round trip incomplete: {st}")
    out["stats"] = {k: st[k] for k in ("swaps_out", "swaps_in",
                                       "copied_bytes", "quantized_pages",
                                       "quant_dispatches", "prefills",
                                       "decode_steps", "split_steps")}
    out["census"] = dict(be.compile_counts())
    out["pool_device_bytes"] = be.pool_device_bytes()
    out["backend"] = be
    return out


def compare(served, ref):
    import numpy as np
    d = np.asarray(served, np.float64) - np.asarray(ref, np.float64)
    rel = float(np.linalg.norm(d) / np.linalg.norm(ref))
    mx = float(np.abs(d).max() / np.std(ref))
    return rel, mx, rel <= REL_RMS and mx <= MAX_ABS_OVER_STD


def check_reference(cfg, params, run, label, skip=()):
    """Served logits vs the float32 reference, on the run's own history."""
    from repro.models.reference import dense_logits
    ok = True
    for (sid, turn), lg in sorted(run["logits"].items()):
        if (sid, turn) in skip:
            continue
        h = run["history"][sid, turn]
        ref = dense_logits(cfg, params, h, [len(h) - 1])[0]
        rel, mx, good = compare(lg, ref)
        ok &= good
        print(f"parity[{label}] {sid} turn {turn + 1} ctx {len(h)}: "
              f"rel_rms {rel:.3e} (<= {REL_RMS}), max_abs/std {mx:.3e} "
              f"(<= {MAX_ABS_OVER_STD}) {'ok' if good else 'FAIL'}",
              flush=True)
    return ok


def report(label, run):
    print(f"{label}: kernel_mode={run['kernel_mode']} pages={run['n_pages']}"
          f" (page {PAGE}) steps={run['steps']} serve_s={run['serve_s']:.2f}"
          f" compile_s={run['compile_s']:.2f} census={run['census']} "
          f"pool_device_bytes={run['pool_device_bytes']} "
          f"stats={run['stats']}", flush=True)


def peak_bytes(devs):
    return [d.memory_stats().get("peak_bytes_in_use") for d in devs]


def one_chip(cfg, model, params, sessions, devs):
    run = serve(cfg, model, params, sessions)
    report("serve", run)
    ma = run["backend"].step_memory(lanes=4, tokens=1, width=128)
    print(f"step memory_analysis (decode bucket B=4, T=128): "
          f"argument={ma.argument_size_in_bytes} "
          f"output={ma.output_size_in_bytes} temp={ma.temp_size_in_bytes} "
          f"alias={ma.alias_size_in_bytes}", flush=True)
    run.pop("backend")
    gc.collect()
    twin = serve(cfg, model, params, sessions, twin=True)
    twin.pop("backend")
    gc.collect()
    report("twin", twin)
    ok = True
    for sid in sessions:
        same = run["tokens"][sid, 1] == twin["tokens"][sid, 1]
        note = ""
        if sid == QUANT:
            ok &= same
            note = " (int8 kernel vs re-inflated pages)"
        print(f"twin {sid} turn 2 tokens {'equal' if same else 'DIFFER'}"
              f"{note}", flush=True)
    ok &= check_reference(cfg, params, run, "1 chip",
                          skip={(QUANT, 1)})
    print(f"peak_bytes_in_use: {peak_bytes(devs)}", flush=True)
    return ok


def four_chips(cfg, model, params, sessions, devs):
    from repro.launch.mesh import make_serving_mesh
    if len(devs) != 4:
        print(f"chip_smoke: --chips 4 needs 4 chips, found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    single = serve(cfg, model, params, sessions)
    single.pop("backend")
    gc.collect()
    report("tp=1", single)
    tp4 = serve(cfg, model, params, sessions,
                mesh=make_serving_mesh(tp=4))
    tp4.pop("backend")
    gc.collect()
    report("tp=4", tp4)
    ok = True
    for key, lg in sorted(tp4["logits"].items()):
        if tp4["history"][key] != single["history"][key]:
            print(f"tp4-vs-1 {key[0]} turn {key[1] + 1}: histories differ "
                  f"(a greedy near-tie in turn 1); checked against the "
                  f"reference only", flush=True)
            continue
        rel, mx, good = compare(lg, single["logits"][key])
        ok &= good
        print(f"tp4-vs-1 {key[0]} turn {key[1] + 1}: rel_rms {rel:.3e} "
              f"(<= {REL_RMS}), max_abs/std {mx:.3e} "
              f"(<= {MAX_ABS_OVER_STD}) {'ok' if good else 'FAIL'}",
              flush=True)
    skip = {(QUANT, 1)}
    ok &= check_reference(cfg, params, tp4, "tp=4", skip=skip)
    ok &= check_reference(cfg, params, single, "tp=1", skip=skip)
    print(f"peak_bytes_in_use: {peak_bytes(devs)}", flush=True)
    return ok


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax

    from repro.models.registry import get_model
    print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
          f"{cache}", flush=True)
    cfg = model_config()
    model = get_model(cfg)
    params = model.init(jax.random.key(args.seed))
    sessions = make_sessions(cfg, args.seed)
    print("sessions: " + ", ".join(
        f"{sid} turn1={len(a)} turn2={len(b)}"
        for sid, (a, b) in sessions.items())
        + f"; {GEN} new tokens per turn; {SWAP} swapped to host and back, "
          f"{QUANT} quantized in place between turns", flush=True)
    phase = four_chips if args.chips == 4 else one_chip
    if not phase(cfg, model, params, sessions, devs):
        print("chip_smoke: a check failed", file=sys.stderr)
        sys.exit(1)
    print(json.dumps(dict(ok=True, device=dict(
        platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs)))))


if __name__ == "__main__":
    main()
