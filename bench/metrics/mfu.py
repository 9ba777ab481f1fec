"""Whole-step model FLOP/s utilization, in %: the training FLOPs the traced
steps require (``work.step_train_flops`` over the mules whose training each
step keeps, by the benchmark's own schedule) over the traced window, over
the chips' bf16 peak."""


def read(ctx):
    s = ctx["summary"]
    if not s or s["window_s"] <= 0 or ctx["required_flops"] <= 0:
        return None
    rate = ctx["required_flops"] / s["window_s"]
    return 100.0 * rate / (s["chips"] * ctx["peak"]["bf16_flops"])
