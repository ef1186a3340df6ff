#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 ...
  python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1 \
      --rates 0.5 1 2        # the knee sweep

For each seed, in one process: set the cell up as a run does, serve its
window, then compare the same sample of served tokens as a run does with
the float32 reference, and read the fp8 control on the same positions (the
token the control puts first, read against the float32 reference).  Each
seed prints one line; the last line is a JSON summary with the largest
program reading (the lower reading of the limit) and the smallest control
reading (its upper reading).  The benchmark's own runs never run this.

With ``--rates`` it sweeps the offered load instead: one window per rate
(each on a fresh set-up of the first seed), reporting how the waiting
queue moved through the window.  The knee is the highest rate at which it
does not grow; the mix's rate is set to 0.8 of it by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import BENCH, ROOT, use_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*")
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 2
    from bench import serve
    from bench.model import load_config
    from bench.traffic import load_mix
    conf, mix = load_config(cell["config"]), load_mix(cell["traffic"])
    chk = mix["check"]
    rows, model = [], None
    if args.rates:
        return sweep(conf, mix, args.seeds[0], args.seconds, args.rates)
    for seed in args.seeds:
        t0 = time.perf_counter()
        c = serve.Cell(conf, mix, seed, args.seconds, model=model)
        model = c.be.model
        c.warm()
        c.fill()
        win = c.window(args.seconds)
        picked = serve.sample(win.records, seed, chk["sample_tokens"],
                              chk["max_requests"])
        params = c.params
        c.free()
        prog = [serve.gaps(conf, params, r) for r in picked]
        ctrl = [serve.gaps(conf, params, r, fp8=True) for r in picked]
        row = dict(seed=seed, requests=len(picked),
                   host=sum(r.host for r in picked),
                   int8=sum(r.int8 for r in picked),
                   tokens=int(sum(len(g) for g in prog)),
                   program=float(max(g.max() for g in prog)),
                   control=float(max(g.max() for g in ctrl)),
                   s=time.perf_counter() - t0)
        rows.append(row)
        print(json.dumps(row), flush=True)
        del params
    print(json.dumps(dict(workload=args.workload, seconds=args.seconds,
                          lower=max(r["program"] for r in rows),
                          upper=min(r["control"] for r in rows),
                          rows=rows)), flush=True)
    return 0


def sweep(conf, mix, seed, seconds, rates) -> int:
    import copy

    import numpy as np

    from bench import serve, tails
    model = None
    for rate in rates:
        m = copy.deepcopy(mix)
        m["arrivals"]["rate_per_s"] = rate
        c = serve.Cell(conf, m, seed, seconds, model=model)
        model = c.be.model
        c.warm()
        c.fill()
        w = c.window(seconds)
        c.free()
        q = np.asarray([n for _, n in w.queue] or [0])
        third = max(1, len(q) // 3)
        arrived = [r for r in w.records if r.at <= seconds]
        print(json.dumps(dict(
            rate=rate, requests=len(arrived),
            first_token=sum(r.first is not None for r in arrived),
            done=sum(r.done is not None for r in arrived),
            queue_first_third=float(q[:third].mean()),
            queue_last_third=float(q[-third:].mean()),
            queue_end=int(q[-1]), steps=w.steps,
            step_ms=1e3 * w.step_s / max(w.steps, 1),
            ttft_p95_ms=1e3 * tails.p95(tails.ttft(w.records, seconds)),
            tbt_p95_ms=1e3 * tails.p95(w.gaps) if w.gaps else None,
            tokens_per_s=w.tokens / seconds, stats={
                k: w.stats[k] for k in ("swaps_in", "swaps_out",
                                        "quantized_pages",
                                        "layer_evictions",
                                        "copied_bytes", "split_steps")},
            late_s=w.late_s)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(BENCH))
    sys.exit(main())
