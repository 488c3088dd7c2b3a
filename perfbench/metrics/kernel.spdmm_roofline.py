"""SpDMM's share of its roofline: the least time of the pass's SpDMM
work (``harness.work``: 2 E F FLOPs; source features, edge list and
output moved once) over the device time of the executables that run
SpDMM, from the profiler trace."""
from harness.trace import roofline_share

# Executables that run SpDMM tiles: the xla tile op and the Pallas
# kernel; inside a larger executable, operations under this scope.
MODULES = [r"^jit__spdmm_xla\b", r"^jit_spdmm\b"]
SCOPES = [r"\back\.spdmm\b"]


def read(ctx):
    c, t = ctx.counters, ctx.trace
    if t is None or not c.get("passes"):
        return None
    got = roofline_share(c["work"]["spdmm"], c["passes"],
                         t.kernel_s(MODULES, SCOPES), ctx.peaks)
    return None if got is None else (got[0], {"bound": got[1]})
