"""Requests per batched pass in the window, from each response's
``batch_size``."""


def read(ctx):
    c = ctx.counters
    if not c.get("batches"):
        return None
    return c["answered"] / c["batches"]
