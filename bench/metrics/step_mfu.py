"""Fused step: the model FLOPs of the window's steps (from their shapes:
held layers, the head at emitting positions, attention over each lane's
context) over the steps' host-clock time at the chip's peak, in %."""


def read(run):
    w = run.window
    if not w.steps or w.step_s <= 0 or not w.step_flops:
        return None
    return 100.0 * w.step_flops / (w.step_s * run.peak["bf16_flops"])
