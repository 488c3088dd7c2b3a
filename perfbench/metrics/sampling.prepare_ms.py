"""Mean host time of ``SamplingService.prepare`` per request (sample,
normalize, bucket, lay out), by the harness's clock around each call."""
from harness.common import mean


def read(ctx):
    v = mean(ctx.counters.get("prepare_s", []))
    return None if v is None else v * 1e3
