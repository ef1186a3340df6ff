"""Quickstart: the SYMPHONY mechanism, for real, in ~70 lines.

Builds a tiny llama-family model and serves the same 3-turn greedy
conversation two ways:

  * vLLM-style recompute — every turn re-prefills the full history through
    the dense model (the stateless baseline);
  * SYMPHONY RealBackend — the serving engine drives paged KV pools:
    continuation prefill (flash_prefill kernel) processes only the NEW
    tokens of each turn against the session's cached pages, decode runs the
    paged_attention kernel through the allocator's block tables.

The generated tokens must be identical while SYMPHONY touches a fraction of
the tokens — the paper's compute saving, executed rather than simulated.

Run:  python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.advisory import InferenceRequest
from repro.core.node_manager import NodeManager
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import get_model
from repro.serving.backend import RealBackend
from repro.serving.cost_model import CostModel, HardwareSpec
from repro.serving.engine import NodeEngine

GEN = 8


def main():
    use_compile_cache()
    cfg = get_config("llama3-8b").reduced(dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    turns = [list(map(int, rng.integers(0, cfg.vocab, rng.integers(8, 16))))
             for _ in range(3)]

    # ---- vLLM-style recompute: every turn reprocesses all history --------
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    history, recompute_tokens, out_recompute = [], 0, []
    for turn in turns:
        history += turn
        toks = jnp.asarray([history], jnp.int32)
        recompute_tokens += toks.shape[1] + GEN
        logits, cache = prefill(params, toks)
        cache = model.grow_cache(cache, GEN)
        outs = []
        for _ in range(GEN):
            nxt = jnp.argmax(logits[:, :cfg.vocab], -1).astype(jnp.int32)
            outs.append(int(nxt[0]))
            logits, cache = decode(params, cache, nxt)
        out_recompute.append(outs)
        history += outs

    # ---- SYMPHONY: RealBackend serves only the NEW tokens of each turn ---
    cost = CostModel(cfg, HardwareSpec(chips_per_replica=1))
    cost.set_param_count(model.param_count())
    mgr = NodeManager(0, cfg, cost)
    backend = RealBackend(cfg, model, params, n_pages=64, page_size=8,
                          mgr=mgr, trace_logits=False)
    eng = NodeEngine(0, cfg, cost, mgr, max_batch=4, backend=backend)
    out_symphony, now = [], 0.0
    for turn in turns:
        req = InferenceRequest(session_id="chat", prompt_tokens=len(turn),
                               max_new_tokens=GEN, prompt_ids=list(turn),
                               cached_tokens=backend.session_tokens("chat"))
        eng.submit(req)
        while eng.waiting or eng.running:
            now += eng.step(now)
        out_symphony.append(req.output_ids)
    symphony_tokens = eng.stats["prefill_tokens"] + \
        backend.stats["decode_steps"]

    assert out_recompute == out_symphony, "continuation must match recompute"
    print(f"turn outputs identical: {out_symphony}")
    print(f"tokens processed — recompute: {recompute_tokens}, "
          f"symphony new-only: {symphony_tokens} "
          f"({1 - symphony_tokens / recompute_tokens:.0%} saved)")
    print(f"backend: {backend.stats['prefills']} paged prefills, "
          f"{backend.stats['decode_steps']} paged decode steps, "
          f"{max(a.used_pages for a in backend.alloc)} pages in use")


if __name__ == "__main__":
    main()
