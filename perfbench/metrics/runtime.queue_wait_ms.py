"""Mean time a request of the window waited in the serving loop before
its batch started executing: the loop's own split
(``Metrics.record_response(queue_wait_s=...)``)."""
from harness.common import mean


def read(ctx):
    v = mean(ctx.counters.get("queue_wait_s", []))
    return None if v is None else v * 1e3
