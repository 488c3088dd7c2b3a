"""Mean host-clock time of one batched pass (``run_batch`` ending in
``block_until_ready``: the responses' ``t_loh``), per batch."""


def read(ctx):
    c = ctx.counters
    if not c.get("batches"):
        return None
    return c["batch_pass_s"] / c["batches"] * 1e3
