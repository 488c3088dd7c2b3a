"""The whole pass's share of the chip's bf16 peak: algorithmic FLOPs of
one pass (``harness.work``, from graph and model shapes) times the
passes of the traced window, over the window and the peak."""
from harness.work import pass_flops


def read(ctx):
    c, t = ctx.counters, ctx.trace
    if t is None or not c.get("passes") or t.window_s <= 0:
        return None
    flops = pass_flops(c["work"]) * c["passes"]
    return 100.0 * flops / (t.window_s * ctx.peaks["bf16_flops_per_s"])
