"""The program's own host spans, laid on a profiler trace's clock.

The program records spans with its tracer (``repro.obs.tracer``) on its
own ``perf_counter_ns`` clock.  ``Tracer.anchor()``, called inside the
profiler session at the start and at the end of the window, writes an
``obs.clock_anchor`` annotation into the profile whose ``perf_ns`` stat
is the tracer-clock time it was opened at, and an instant of the same
name and stamp into the tracer's events.  The two anchors give the line
from the tracer's clock to the trace's; every span maps through it, one
closed in another thread included.

Spans read (the program's names):

* per request: ``sampling.sample``, ``sampling.layout`` (in
  ``SamplingService.prepare``), ``serve.batching``, ``serve.handoff``,
  ``serve.respond`` and their parent ``serve.request`` (``ServeLoop``);
* per batch, on its overlay's thread: ``exec.batch_stage``,
  ``exec.batch_pass`` and ``exec.batch_unstack`` (``Engine.submit_batch``);
* on the device: the batched pass's executable, ``jit_batched_pass``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from repro.obs.tracer import Tracer, set_tracer

from .common import mean
from .trace import DeviceTrace, Interval

ANCHOR = "obs.clock_anchor"
BATCHED_PASS = r"^jit_batched_pass\b"
# Spans that a parent span holds whole; idle time is labelled by leaves.
PARENTS = ("serve.request",)
# The benchmark's spans that the program's spans split finer: a gap is
# labelled by the program's where it has them.
REFINED = ("prepare", "pass")

# Metric name -> the span whose mean duration it reads, in ms.
SPAN_METRICS = {
    "sampling.sample_ms": "sampling.sample",
    "sampling.layout_ms": "sampling.layout",
    "runtime.batching_ms": "serve.batching",
    "runtime.handoff_ms": "serve.handoff",
    "exec.batch_stage_ms": "exec.batch_stage",
    "runtime.respond_ms": "serve.respond",
}


@dataclasses.dataclass
class Span:
    start: int          # ns, on the trace's clock
    end: int
    name: str
    args: dict
    tid: int = 0        # the tracer's thread (or track) id

    @property
    def dur(self) -> int:
        return self.end - self.start


class Clock:
    """The line from the tracer's ``perf_counter_ns`` clock to the
    trace's, through the anchors ``[(trace_ns, perf_ns), ...]`` in the
    order they were written: the first and the last (one anchor: an
    offset alone)."""

    def __init__(self, anchors: Sequence[Tuple[int, int]]) -> None:
        if not anchors:
            raise ValueError("no clock anchors")
        (self.y0, self.x0), (y1, x1) = anchors[0], anchors[-1]
        self.slope = (y1 - self.y0) / (x1 - self.x0) if x1 != self.x0 \
            else 1.0
        # trace time elapsed between the anchors less tracer time
        self.drift_ns = (y1 - self.y0) - (x1 - self.x0)

    def __call__(self, perf_ns: float) -> int:
        return int(round(self.y0 + self.slope * (perf_ns - self.x0)))


class ProgramTracer:
    """The program's tracer over a traced window: ``start()`` installs a
    fresh one, ``anchor()`` stamps its clock on the running profile,
    ``stop()`` puts back the tracer that was there and keeps the events."""

    def __init__(self) -> None:
        self.events: List[dict] = []
        self._tracer = self._prev = None

    def start(self) -> None:
        self._tracer = Tracer()
        self._prev = set_tracer(self._tracer)

    def anchor(self) -> None:
        self._tracer.anchor()

    def stop(self) -> None:
        if self._tracer is None:
            return
        set_tracer(self._prev)
        self.events = self._tracer.events()
        self._tracer = None


def read_anchors(path: str) -> List[Tuple[int, int]]:
    """``[(trace_ns, perf_ns), ...]`` of the clock anchors in an
    ``.xplane.pb`` file, in trace order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != ANCHOR:
                    continue
                stats = dict(ev.stats)
                if "perf_ns" in stats:
                    out.append((int(ev.start_ns), int(stats["perf_ns"])))
    return sorted(out)


def mapped(events: Sequence[dict], anchors: Sequence[Tuple[int, int]]
           ) -> Tuple[List[Span], Optional[Clock]]:
    """The tracer's complete events as spans on the trace's clock, and
    the clock that mapped them; ``([], None)`` without anchors on both
    sides."""
    mine = [e for e in events if e.get("name") == ANCHOR and e["ph"] == "i"]
    if not anchors or not mine:
        return [], None
    clock = Clock(anchors)
    # tracer ts are microseconds since its start; an anchor instant
    # holds both its ts and its perf_counter_ns stamp
    base = mine[0]["args"]["perf_ns"] - mine[0]["ts"] * 1e3
    spans = []
    for e in events:
        if e["ph"] != "X":
            continue
        t0 = base + e["ts"] * 1e3
        spans.append(Span(clock(t0), clock(t0 + e["dur"] * 1e3), e["name"],
                          e.get("args", {}), e.get("tid", 0)))
    spans.sort(key=lambda s: s.start)
    return spans, clock


def in_window(spans: Sequence[Span], window: Tuple[int, int]
              ) -> List[Span]:
    lo, hi = window
    return [s for s in spans if lo <= s.start < hi]


def mean_ms(spans: Sequence[Span], name: str) -> Optional[float]:
    v = mean([s.dur for s in spans if s.name == name])
    return None if v is None else v * 1e-6


def span_readings(spans: Sequence[Span]) -> Dict[str, Optional[float]]:
    """Each span metric: the mean of its span, in ms (``None`` where the
    span is absent)."""
    return {m: mean_ms(spans, name) for m, name in SPAN_METRICS.items()}


def batch_device_ms(trace: DeviceTrace) -> Optional[float]:
    """Mean device time of one batched pass: executables named
    ``jit_batched_pass`` inside the window, over devices."""
    lo, hi = trace.window
    durs = [m.dur for d in trace.devices for m in d.modules
            if m.end > lo and m.start < hi and re.search(BATCHED_PASS,
                                                         m.name)]
    return mean(durs) * 1e-6 if durs else None


def _labels(trace: DeviceTrace, spans: Sequence[Span]) -> List[Interval]:
    """The host spans a gap can be labelled by: the program's, less the
    parents, and the benchmark's, less those the program's split."""
    mine = [Interval(s.start, s.end, s.name) for s in spans
            if s.name not in PARENTS]
    theirs = [h for h in trace.host_spans
              if not (mine and h.name in REFINED)]
    return theirs + mine


def idle_gaps(trace: DeviceTrace, spans: Sequence[Span], n: int = 64
              ) -> List[List]:
    """Device idle time in the window labelled by the span, the
    program's or the benchmark's, that overlaps each gap the most:
    ``DeviceTrace.idle_gaps`` over both."""
    return DeviceTrace(trace.devices, _labels(trace, spans),
                       trace.window).idle_gaps(n)
