"""The edge softmax's share of its roofline: the least time of the
pass's edge softmax over the device time of its executable,
``jit_edge_softmax``, from the profiler trace.

The least work is counted here.  Per GAT layer, over the pass's ``E``
edges (self loops in): about 5 FLOPs an edge (max, subtract, exp, sum,
divide); bytes that read and write one score an edge and read its
destination id, 4 bytes each.  ``E`` and the layers are the pass's
pair-sum scoring (``harness.work``: one call of ``E`` FLOPs per GAT
layer), whose scores each layer's softmax normalizes."""
from harness.trace import roofline_share
from harness.work import F32

MODULES = [r"^jit_edge_softmax\b"]


def work(sddmm_calls):
    """``[(flops, bytes)]`` of the edge softmax, one per scoring call."""
    return [(5.0 * e, F32 * 3 * e) for e, _ in sddmm_calls]


def read(ctx):
    c, t = ctx.counters, ctx.trace
    if t is None or not c.get("passes"):
        return None
    got = roofline_share(work(c["work"]["sddmm"]), c["passes"],
                         t.kernel_s(MODULES), ctx.peaks)
    return None if got is None else (got[0], {"bound": got[1]})
