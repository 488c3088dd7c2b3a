"""Share of device-busy time spent in executables that belong to no
ACK kernel on the eager path: pads, ``where``, the edge scatter, the
concatenate that assembles a layer's output."""
import re

# Every executable the ACK runs a tile kernel in (core/ack.py and the
# Pallas kernels).
ACK_MODULES = [r"^jit__gemm_xla\b", r"^jit__spdmm_xla\b",
               r"^jit__sddmm_xla\b", r"^jit__sddmm_pair_xla\b",
               r"^jit__gemm_agg_xla\b", r"^jit_densify_tile\b",
               r"^jit__vadd_xla\b", r"^jit__act_xla\b", r"^jit__affine_xla\b",
               r"^jit_gemm\b", r"^jit_spdmm\b", r"^jit_sddmm\b"]


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    per = t.module_s()
    total = sum(per.values())
    if total <= 0:
        return None
    glue = sum(s for name, s in per.items()
               if not any(re.search(p, name) for p in ACK_MODULES))
    return 100.0 * glue / total
