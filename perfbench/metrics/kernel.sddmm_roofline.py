"""SDDMM's share of its roofline: the least time of the pass's edge
scoring (``harness.work``) over the device time of the executables
that run SDDMM, from the profiler trace."""
from harness.trace import roofline_share

# Executables that run SDDMM tiles (GAT's pair-sum form and the inner
# product) and the Pallas kernel; inside a larger executable,
# operations under this scope.
MODULES = [r"^jit__sddmm_xla\b", r"^jit__sddmm_pair_xla\b", r"^jit_sddmm\b"]
SCOPES = [r"\back\.sddmm\b"]


def read(ctx):
    c, t = ctx.counters, ctx.trace
    if t is None or not c.get("passes"):
        return None
    got = roofline_share(c["work"]["sddmm"], c["passes"],
                         t.kernel_s(MODULES, SCOPES), ctx.peaks)
    return None if got is None else (got[0], {"bound": got[1]})
