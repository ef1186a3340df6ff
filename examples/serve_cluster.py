"""End-to-end driver: the full multi-node SYMPHONY scenario on the REAL
backend, through the same `ClusterRuntime` event loop that runs the
paper-scale simulations.

A 3-node cluster on CPU serves interleaved multi-turn sessions with an
actual tiny model — real tokens, real paged KV migrating through the tiered
store (HBM = jnp page pools, host = numpy staging, disk = per-node .npz
spools), flash_prefill on the continuation path and the paged_attention
Pallas kernel (interpret mode) on the decode path.

Each turn: an advisory fires first, the scheduler plans placement, and the
target node's manager migrates + promotes the session KV *off the critical
path* (real export/import page copies between nodes).  The scenario shape
(2 sessions, 3 nodes) guarantees both headline events deterministically:

* turn 1 occupies nodes 0 and 1, so node 2 is idle — the first turn-2
  advisory always plans it (strictly smallest load key) and its session's
  KV migrates across nodes for real;
* after session s0's turn 2 completes, the node that served it is killed:
  its fast tiers are physically lost, stranded requests are replayed from
  turn start, and orphaned KV is recovered from the dead node's disk spool
  (or recomputed from full history when no spool copy exists).

Self-verifying: every session's token stream must match the dense
full-recompute reference exactly, across migration AND the failure.

Run:  python examples/serve_cluster.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import get_model
from repro.serving.cost_model import HardwareSpec
from repro.serving.scenario import (MultiTurnRealTrace, dense_reference,
                                    session_outputs)
from repro.serving.simulator import ClusterRuntime

N_NODES, N_SESSIONS, N_TURNS, GEN = 3, 2, 4, 8


def main():
    use_compile_cache()
    cfg = get_config("llama3-8b").reduced(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))

    rt = ClusterRuntime(cfg, n_nodes=N_NODES, policy="symphony",
                        hw=HardwareSpec(chips_per_replica=1),
                        max_batch=8, mode="real", model=model,
                        params=params, n_pages=64, page_size=8,
                        trace_logits=False)   # token-exact verify; no trail
    trace = MultiTurnRealTrace(cfg, n_sessions=N_SESSIONS, n_turns=N_TURNS,
                               prompt_len=10, gen=GEN, seed=1,
                               fail_after_turn=2)
    try:
        _run_and_verify(rt, trace, cfg, model, params)
    finally:
        rt.cleanup()       # drop the spool even when verification fails


def _run_and_verify(rt, trace, cfg, model, params):
    res = rt.run(trace)
    m = res.metrics()

    migrations = sum(n["migrations"] for n in m["per_node"].values())
    recoveries = sum(n["recoveries"] for n in m["per_node"].values())
    copied = sum(n.get("copied_bytes", 0) for n in m["per_node"].values())
    dead = sorted(i for i, st in rt.sched.nodes.items() if not st.alive)
    print(f"served {m['completed']} turns across {N_NODES} real nodes "
          f"(node {dead} failed mid-run)")
    print(f"real page traffic: {migrations} session migrations, "
          f"{recoveries} spool recoveries, {copied / 1024:.0f} KiB copied")
    print(f"ttft mean {m['ttft_mean_s']*1e3:.0f} ms   "
          f"tpot mean {m['tpot_mean_s']*1e3:.0f} ms   "
          f"imbalance ratio {m['imbalance']['ratio']:.2f}")

    # ---- verify EVERY session token-for-token against dense recompute ----
    got = session_outputs(res)
    want = dense_reference(cfg, model, params, trace.prompts, GEN)
    assert got == want, (got, want)
    assert migrations >= 1, "expected at least one advisory-driven migration"
    assert dead, "expected the injected node failure to have happened"
    for mgr in rt.managers.values():
        mgr.store.check()
    print(f"all {N_SESSIONS} sessions match the dense recompute reference "
          f"across {N_TURNS} turns (incl. cross-node migration + failure "
          f"recovery: {recoveries} from spool)")


if __name__ == "__main__":
    main()
