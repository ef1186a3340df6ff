#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this machine holds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, the architecture module the
configuration's ``"reference"`` names, ``bench/arch/<reference>.py``, and
one reader per per-layer metric in ``bench/metrics/<metric>.py``.  A
cell, mix, architecture or metric is added by adding a file and an entry;
this script needs no edit.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window.  Every run ends by comparing the tokens the window served
with the float32 reference; the numbers compared, each beside its limit,
are the last lines on standard error and the last key of the result,
which is the last line on standard output.

The script exits non-zero and prints no result when JAX finds no TPU or
fewer chips than the cell asks for: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_reader(name: str):
    from bench.model import load_file
    return load_file(BENCH / "metrics" / f"{name}.py").read


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def use_compile_cache() -> str:
    """JAX's persistent cache at one fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), caching every program and
    evicting none: a cell's lattice has to stay whole from run to run,
    and a cache without eviction takes no lock, so programs compile in
    parallel.  A Pallas kernel's body carries the source locations of
    its call stack, and they enter the cache key: with full tracebacks
    every entry script (this one, ``calibrate.py``) keyed its own copy of
    each program, so locations keep the innermost frame only."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def count_compiles():
    """A counter of programs compiled or loaded from the persistent cache
    from here on, with their seconds, read through JAX's monitoring."""
    import jax
    box = {"n": 0, "s": 0.0}
    events = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def on(event, secs, **_kw):
        if event in events:
            box["n"] += 1
            box["s"] += secs
    jax.monitoring.register_event_duration_secs_listener(on)
    return box


def measure(spec: dict, cell: dict, conf: dict, mix: dict, seed: int,
            seconds: float, traced: bool, peak: dict, devs, *,
            t_start=None, **run_kw) -> dict:
    """One run of ``cell`` on ``devs``, as the result line's dict.  Prints
    the run's account and, last, the numbers compared to standard error.
    ``run_kw`` goes to `serve.run` (the tests shrink and break the path)."""
    from bench import serve, trace
    name = cell["name"]
    compiles = count_compiles()
    trace_dir = None
    if traced:
        trace_dir = str(ROOT / "results" / "trace" / name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    res = serve.run(conf, mix, seed, seconds, trace_dir=trace_dir,
                    t_start=t_start, compiles=compiles, **run_kw)
    win = res["window"]
    print(f"run.py: set-up {res['setup_s']:.3f} s (weights "
          f"{res['params_s']:.3f} s; {res['warmed']} programs warmed "
          f"{ {k: round(v, 3) for k, v in res['warm_s'].items()} }; "
          f"{res['fill_s']:.3f} s of history fill, "
          f"{res['history_tokens']} tokens in {res['sessions']} "
          f"sessions); window {res['wall_s']:.3f} s: {win.steps} steps, "
          f"{len(win.records)} requests, {win.tokens} tokens, submits up "
          f"to {1e3 * win.late_s:.1f} ms late, TTFT p95 "
          f"{res['ttft_p95_ms']:.1f} ms; programs compiled or "
          f"loaded in the window {res['window_compiles']}; tier stats "
          f"{win.stats}; compared "
          f"{res['picked']} requests {res['picked_kinds']} in "
          f"{res['check_s']:.3f} s; peak device bytes {res['peak_bytes']}",
          file=sys.stderr, flush=True)
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), memory_peak_bytes=res["peak_bytes"])
    out = dict(correct=res["correct"], attempted=res["attempted"],
               failed=res["failed"])
    metrics = {}
    if not traced:
        for m in spec["end_to_end"]:
            if applies(m, name):
                v = res["end_to_end"].get(m["name"])
                if v is not None:
                    metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        tr = trace.load(trace_dir)
        lo, hi = trace.extent(tr["spans"])
        run = SimpleNamespace(window=win, trace=tr, span=(lo, hi),
                              peak=peak, cfg=res["cfg"])
        for m in spec["per_layer"]:
            if applies(m, name):
                v = load_reader(m["name"])(run)
                if v is not None:
                    metrics[m["name"]] = dict(value=v, unit=m["unit"])
        device.update(busy_s=trace.busy_ns(tr["ops"], lo, hi) / 1e9,
                      window_s=(hi - lo) / 1e9)
        out["breakdown"] = dict(
            device_ops=[list(x) for x in trace.top_ops(tr["ops"], lo, hi)],
            idle_gaps=[list(x) for x in
                       trace.idle_gaps(tr["ops"], tr["spans"], lo, hi)])
        print(f"run.py: trace lines {tr['lines']}; ops span "
              f"{min((s for _, s, _ in tr['ops']), default=None)}.."
              f"{max((s for _, s, _ in tr['ops']), default=None)}, host "
              f"spans {lo}..{hi}", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["metrics"] = metrics
    out["device"] = device
    # programs compiled or loaded inside the window: 0 when set-up warmed
    # the whole lattice; anything else is a gap in it, timed as service
    out["window_compiles"] = res["window_compiles"]["n"]
    out["compared"] = {c["name"]: dict(value=c["value"], limit=c["limit"],
                                       rule=c["rule"])
                       for c in res["compared"]}
    for c in res["compared"]:
        print(f"compared {c['name']}: {c['value']!r} (limit "
              f"{c['rule']} {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench.model import arch_path, load_config
    conf = load_config(cell["config"])
    if not arch_path(conf).is_file():
        print(f"run.py: configuration {cell['config']!r} names the "
              f"reference {conf['reference']!r}, and there is no "
              f"{arch_path(conf)}", file=sys.stderr)
        return 2
    import jax
    cache = use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"run.py: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if kind not in peaks:
        print(f"run.py: no peaks for device kind {kind!r} in "
              f"bench/peaks.json", file=sys.stderr)
        return 2

    from bench.traffic import load_mix
    print(f"run.py: {args.workload} seed {args.seed} on {kind} "
          f"x{len(devs)}; compile cache {cache}", file=sys.stderr,
          flush=True)
    out = measure(spec, cell, conf, load_mix(cell["traffic"]), args.seed,
                  args.seconds, bool(args.trace), peaks[kind], devs,
                  t_start=t_start)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
