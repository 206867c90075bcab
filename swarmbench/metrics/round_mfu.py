"""Round program: the whole round's model FLOPs (local steps forward and
backward, the gate's two forward passes over the valid validation images;
`swarmbench.flops`) times the rounds completed in the window, over the
window and the chips' bf16 peak, in percent."""

from swarmbench import flops


def read(ctx):
    if not ctx["rounds"]:
        return None
    peak = flops.peak(ctx["device_kind"])["bf16_flops_per_s"]
    done = ctx["rounds"] * ctx["model_flops_per_round"]
    return 100.0 * done / ctx["window_s"] / (ctx["chips"] * peak)
