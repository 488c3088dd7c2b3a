"""Share of the traced window with no operation running on the device:
1 - (union of device-op intervals) / window, from the profiler trace."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share()
