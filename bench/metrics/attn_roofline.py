"""Attention kernel: the least time the chip could take for the window's
attention, step by step the larger of its FLOPs over peak and its bytes
(the relevant pages the clamped index maps fetch) over bandwidth, over the
kernel's device time in the trace, in %."""
import sys

from bench import trace

NEEDLES = ("paged_chunk_attention", "_chunk_kernel")


def read(run):
    if run.trace is None or not run.window.attn_steps:
        return None
    t, n = trace.time_in(run.trace["ops"], NEEDLES, *run.span)
    if not n:
        return None
    pf, bw = run.peak["bf16_flops"], run.peak["hbm_bytes_per_s"]
    bound = by_bytes = 0.0
    for f, b in run.window.attn_steps:
        bound += max(f / pf, b / bw)
        by_bytes += b / bw >= f / pf
    print(f"attn_roofline: {n} kernel calls, {t / 1e9:.6f} s; bound by "
          f"bytes in {int(by_bytes)} of {len(run.window.attn_steps)} steps",
          file=sys.stderr)
    return 100.0 * bound / (t / 1e9)
