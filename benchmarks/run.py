"""Benchmark entrypoint: one function per paper table/figure + the roofline
table.  Prints ``name,us_per_call,derived`` CSV (ratios/fractions are scaled
by 1e6 into the us column; the derived field says what they mean).

``--serving`` aggregates the serving artifacts
(results/bench/BENCH_step.json + BENCH_cluster.json, plus
BENCH_sharing.json, BENCH_recurrent.json, BENCH_quant.json and
BENCH_hetero.json when present) into the
top-level ``results/bench/BENCH_serving.json`` scorecard: steady-state TBT
median/p99, the long-prompt-interference TBT bound, the async swap-in
overlap profile (advisory-led residual stall must stay ~0), the
prefix-sharing footprint ratio (peak pages over the unshared cost for a
1000-session shared-system-prompt cohort — must stay sublinear), the
recurrent-state profile (O(1) slot-blob swap bytes vs linear paged KV and
the sessions/node headroom multiple, token-exact parity required), the
quantized-KV-tier profile (in-place int8 session headroom over the fp
baseline, kernel parity error, and the sim quantize-vs-swap A/B), the
heterogeneous-skew profile (1 long + 15 short decode lanes: skewed p99
over a context-matched homogeneous baseline must stay <= 1.5x with zero
measured compiles — the page-walk-elimination observable), cluster
throughput, compile counts, and copied bytes — the one file CI uploads and
gates (decode-p99-under-interference must not regress vs the committed
copy; footprint ratio bounded absolutely)."""
from __future__ import annotations

import argparse
import json
import sys
import time


def _mesh_summary(mesh):
    """Tensor-parallel serving section of the scorecard (None when the
    step artifact predates the mesh mode)."""
    if not mesh:
        return None
    return dict(
        tp=mesh.get("tp"),
        pool_spec=mesh.get("meshed", {}).get("pool_spec"),
        pool_bytes_ratio=mesh.get("pool_bytes_ratio"),
        pool_device_bytes=mesh.get("meshed", {}).get("pool_device_bytes"),
        pool_device_bytes_tp1=mesh.get("single_device",
                                       {}).get("pool_device_bytes"),
        decode_ms_mean=mesh.get("meshed", {}).get("decode_ms_mean"),
        decode_ms_mean_tp1=mesh.get("single_device",
                                    {}).get("decode_ms_mean"),
        measured_compiles=mesh.get("meshed", {}).get("measured_compiles"),
        compile_counts=mesh.get("compile_counts"),
    )


def aggregate_serving() -> dict:
    """Fold BENCH_step.json + BENCH_cluster.json into BENCH_serving.json.
    Both inputs must already exist (CI's earlier steps emit them)."""
    from benchmarks.common import RESULTS, save

    step_f = RESULTS / "BENCH_step.json"
    cluster_f = RESULTS / "BENCH_cluster.json"
    for f in (step_f, cluster_f):
        if not f.exists():
            raise SystemExit(
                f"{f} missing — run `python -m benchmarks.kernel_bench "
                f"--step` and `python -m benchmarks.fig_serving --cluster` "
                f"first")
    step = json.loads(step_f.read_text())
    cluster = json.loads(cluster_f.read_text())
    sharing_f = RESULTS / "BENCH_sharing.json"
    sharing = json.loads(sharing_f.read_text()) if sharing_f.exists() \
        else None      # optional locally; CI always emits it first
    recurrent_f = RESULTS / "BENCH_recurrent.json"
    recurrent = json.loads(recurrent_f.read_text()) \
        if recurrent_f.exists() else None    # optional locally, like sharing
    quant_f = RESULTS / "BENCH_quant.json"
    quant = json.loads(quant_f.read_text()) if quant_f.exists() \
        else None                            # optional locally, like sharing
    hetero_f = RESULTS / "BENCH_hetero.json"
    hetero = json.loads(hetero_f.read_text()) if hetero_f.exists() \
        else None                            # optional locally, like sharing

    cfgs = list(step["configs"].values())
    medians = sorted(c["decode_ms_median"] for c in cfgs
                     if c.get("decode_ms_median") is not None)
    p90s = sorted(c["decode_ms_p90"] for c in cfgs
                  if c.get("decode_ms_p90") is not None)
    inter = step.get("interference", {})
    over = step.get("overlap", {})
    sym = cluster.get("symphony", {})
    per_node = sym.get("per_node", {})
    out = dict(
        steady=dict(
            decode_ms_median=(medians[len(medians) // 2] if medians
                              else None),
            decode_ms_p90_worst=(p90s[-1] if p90s else None),
            steady_steps=sum(c.get("steady_steps", 0) for c in cfgs),
            compile_steps=sum(c.get("compile_steps", 0) for c in cfgs),
        ),
        interference=dict(
            tbt_median_ms=inter.get("tbt_median_ms"),
            tbt_p99_ms=inter.get("tbt_p99_ms"),
            steady_median_ms=inter.get("steady_median_ms"),
            steady_p99_ms=inter.get("steady_p99_ms"),
            tbt_median_over_steady=inter.get("tbt_median_over_steady"),
            tbt_p99_over_steady_p99=inter.get("tbt_p99_over_steady_p99"),
            interference_compiles=inter.get("interference_compiles"),
            token_budget=inter.get("token_budget"),
            prompt_len=inter.get("prompt_len"),
        ),
        overlap=dict(
            stall_cold_ms=over.get("stall_cold_ms"),
            stall_warm_ms=over.get("stall_warm_ms"),
            overlap_ratio=over.get("overlap_ratio"),
            ctx_len=over.get("ctx_len"),
            lead_steps=over.get("lead_steps"),
        ),
        cluster=dict(
            throughput_rps=sym.get("throughput_rps"),
            ttft_mean_s=sym.get("ttft_mean_s"),
            ttft_p99_s=sym.get("ttft_p99_s"),
            tpot_mean_s=sym.get("tpot_mean_s"),
            stall_s=sum(n.get("stall_s", 0.0) for n in per_node.values()),
            preemptions=sum(n.get("preemptions", 0)
                            for n in per_node.values()),
        ),
        sharing=None if sharing is None else dict(
            n_sessions=sharing.get("n_sessions"),
            footprint_ratio=sharing.get("footprint_ratio"),
            peak_used_pages=sharing.get("peak_used_pages"),
            unshared_pages=sharing.get("unshared_pages"),
            prefix_hits=sharing.get("prefix_hits"),
            shared_tokens=sharing.get("shared_tokens"),
            cow_forks=sharing.get("cow_forks"),
            parity_ok=sharing.get("parity_ok"),
        ),
        mesh=_mesh_summary(step.get("mesh")),
        recurrent=None if recurrent is None else dict(
            ctx_len=recurrent.get("ctx_len"),
            stall_cold_kv_ms=recurrent.get("kv", {}).get("stall_cold_ms"),
            stall_cold_state_ms=recurrent.get("recurrent",
                                              {}).get("stall_cold_ms"),
            kv_resident_bytes=recurrent.get("kv", {}).get("resident_bytes"),
            state_resident_bytes=recurrent.get("recurrent",
                                               {}).get("resident_bytes"),
            swap_bytes_ratio=recurrent.get("swap_bytes_ratio"),
            state_bytes_flat=recurrent.get("state_bytes_flat"),
            headroom_tokens=recurrent.get("headroom_tokens"),
            headroom_ratio=recurrent.get("headroom_ratio"),
            parity_ok=recurrent.get("parity_ok"),
        ),
        quant=None if quant is None else dict(
            headroom_ratio=quant.get("headroom", {}).get("ratio"),
            peak_resident_quant=quant.get("headroom", {}).get(
                "quant", {}).get("peak_resident_sessions"),
            peak_resident_fp=quant.get("headroom", {}).get(
                "fp", {}).get("peak_resident_sessions"),
            steady_compiles=quant.get("headroom", {}).get(
                "quant", {}).get("steady_compiles"),
            parity_quant_vs_fp=quant.get("parity", {}).get("quant_vs_fp"),
            parity_pallas_vs_oracle=quant.get("parity",
                                              {}).get("pallas_vs_oracle"),
            sim_transfer_bytes_ratio=quant.get("sim_ab", {}).get(
                "transfer_bytes_ratio"),
            sim_tpot_ratio=quant.get("sim_ab", {}).get("tpot_ratio"),
            sim_quantized_sessions=quant.get("sim_ab", {}).get(
                "quantize_on", {}).get("quantized_sessions"),
        ),
        hetero=None if hetero is None else dict(
            long_len=hetero.get("long_len"),
            p99_ratio=hetero.get("p99_ratio"),
            p50_ratio=hetero.get("p50_ratio"),
            skew_p99_ms=hetero.get("skew", {}).get("p99_ms"),
            homog_p99_ms=hetero.get("homog", {}).get("p99_ms"),
            dma_pages_per_step=hetero.get("skew",
                                          {}).get("dma_pages_per_step"),
            grid_over_fused=hetero.get("grid_over_fused"),
            split_steps=hetero.get("skew", {}).get("split_steps"),
            measured_compiles=hetero.get("measured_compiles"),
        ),
        compile_counts=step.get("compile_counts", {}),
        copied_bytes=sum(c.get("copied_bytes", 0.0) for c in cfgs),
    )
    save("BENCH_serving", out)
    print(json.dumps(out, indent=1))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slow)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--serving", action="store_true",
                    help="aggregate BENCH_step + BENCH_cluster into "
                         "BENCH_serving.json and exit")
    args = ap.parse_args()
    if args.serving:
        aggregate_serving()
        return

    from benchmarks import fig_serving, fig_tokens
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks.roofline_table import emit_roofline
    from benchmarks.kernel_bench import (bench_hetero, bench_kernels,
                                         bench_quant, bench_recurrent,
                                         bench_sharing, bench_step)

    t0 = time.time()
    sections = {
        "trace": lambda: fig_tokens.fig4_6_trace_stats(),
        "fig07": lambda: fig_tokens.fig7_recompute_vs_swap(),
        "fig08": lambda: fig_tokens.fig8_migration(
            users=1024 if args.full else 256),
        "fig12": lambda: fig_serving.fig12_13(
            "llama3-8b",
            users_list=(64, 256, 1024) if args.full else (64, 1024),
            quick=not args.full),
        "fig13": lambda: fig_serving.fig12_13(
            "codeqwen1.5-7b",          # MHA kv=32: 4x KV/token, stands in
            users_list=(64, 256) if args.full else (64,),   # for the 13B-class
            quick=not args.full),      # memory pressure of paper Fig 13
        "fig14": lambda: fig_serving.fig14(
            users=1024 if args.full else 256),
        "fig15": lambda: fig_serving.fig15(),
        "fig16": lambda: fig_serving.fig16(),
        "fig17": lambda: fig_serving.fig17(),
        "fig18": lambda: fig_serving.fig18(
            fracs=(0.1, 0.3, 0.5) if args.full else (0.1, 0.5)),
        "roofline": emit_roofline,
        "kernels": bench_kernels,
        "step": bench_step,
        "hetero": bench_hetero,
        "sharing": bench_sharing,
        "recurrent": bench_recurrent,
        "quant": bench_quant,
    }
    for name, fn in sections.items():
        if args.only and args.only != name:
            continue
        print(f"# --- {name} ---", flush=True)
        fn()                  # a failed section fails the run
    print(f"# total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
