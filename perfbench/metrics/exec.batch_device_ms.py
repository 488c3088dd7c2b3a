"""Mean device time of one batched pass: the executables named
``jit_batched_pass`` (``BinaryExecutor.run_batch``) inside the traced
window.  Silent where no executable has that name."""
from harness.program import batch_device_ms


def read(ctx):
    if ctx.trace is None:
        return None
    return batch_device_ms(ctx.trace)
