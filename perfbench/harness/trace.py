"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

A traced run wraps its measured window in a host span named
``window``.  From the trace this module takes, per device plane:

* the operations that ran (the ``XLA Ops`` line) and the executables
  they belong to (the ``XLA Modules`` line);
* the host spans the benchmark's own files wrote around each pass,
  prepare, submit and idle wait (``jax.profiler.TraceAnnotation``).

and computes busy time as the union of operation intervals inside the
window, kernel time as the summed device duration of the executables
(or, inside a larger executable, the operations under a named scope)
that a kernel's patterns match, and the device's idle gaps labelled by
the host span that covers most of each gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Host lines whose first events hold none of the benchmark's spans are
# the runtime's own threads, and are not read further.
HOST_PEEK = 2000
# Stats of an operation event that may carry its named-scope path.
_SCOPE_STATS = ("tf_op", "long_name", "name_scope", "scope")


@dataclasses.dataclass
class Interval:
    start: int          # ns, on the trace's common clock
    end: int
    name: str
    scope: str = ""     # named-scope path of an operation, if recorded

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: List[Interval]
    modules: List[Interval]


def union_ns(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
             ) -> List[Tuple[int, int]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as sorted
    disjoint intervals."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                   if e > lo and s < hi)
    out: List[Tuple[int, int]] = []
    for s, e in spans:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _matches(name: str, patterns: Sequence[str]) -> bool:
    return any(re.search(p, name) for p in patterns)


class DeviceTrace:
    """One traced window: device planes, host spans and the window."""

    def __init__(self, devices: List[DevicePlane],
                 host_spans: List[Interval], window: Tuple[int, int]
                 ) -> None:
        self.window = window
        self.host_spans = sorted(host_spans, key=lambda s: s.start)
        # Only planes on which something ran inside the window count.
        lo, hi = window
        self.devices = [d for d in devices
                        if any(o.end > lo and o.start < hi for o in d.ops)]

    # -- the window and busy time ------------------------------------- #
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, dev: DevicePlane) -> List[Tuple[int, int]]:
        return union_ns(((o.start, o.end) for o in dev.ops), *self.window)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(sum(e - s for s, e in self._busy(d)) for d in self.devices)
        return tot / len(self.devices) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    # -- attribution --------------------------------------------------- #
    def _in_window(self, ivs: List[Interval]) -> List[Interval]:
        lo, hi = self.window
        return [i for i in ivs if i.end > lo and i.start < hi]

    def kernel_s(self, modules: Sequence[str], scopes: Sequence[str] = ()
                 ) -> float:
        """Device seconds of one kernel, averaged over devices: whole
        executables whose name matches ``modules``, plus operations whose
        named scope matches ``scopes`` inside executables that do not."""
        if not self.devices:
            return 0.0
        tot = 0
        for d in self.devices:
            mods = self._in_window(d.modules)
            hit = [m for m in mods if _matches(m.name, modules)]
            tot += sum(m.dur for m in hit)
            if scopes:
                starts = [m.start for m in hit]
                for o in self._in_window(d.ops):
                    if not _matches(o.scope or o.name, scopes):
                        continue
                    i = bisect.bisect_right(starts, o.start) - 1
                    if i >= 0 and hit[i].end >= o.end:
                        continue        # counted with its executable
                    tot += o.dur
        return tot / len(self.devices) * 1e-9

    def module_s(self) -> Dict[str, float]:
        """Device seconds per executable name, summed over devices."""
        out: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for m in self._in_window(d.modules):
                out[m.name] += m.dur * 1e-9
        return dict(out)

    def top_ops(self, n: int = 10) -> List[List]:
        """The device executables (or, where one executable holds all the
        work, its operations) that took most time: ``[[name, s], ...]``."""
        per = self.module_s()
        if len(per) < 3:
            per = defaultdict(float)
            for d in self.devices:
                for o in self._in_window(d.ops):
                    per[o.name] += o.dur * 1e-9
        return [[k, v] for k, v in
                sorted(per.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Device idle time inside the window, summed per label of the
        host span that overlaps each gap the most (``"none"`` where no
        span does): ``[[label, s], ...]``, the largest first."""
        per: Dict[str, float] = defaultdict(float)
        spans = self.host_spans
        starts = [s.start for s in spans]
        longest = max((s.dur for s in spans), default=0)
        for d in self.devices:
            busy = self._busy(d)
            edges = [self.window[0]] + [x for iv in busy for x in iv] \
                + [self.window[1]]
            for gs, ge in zip(edges[0::2], edges[1::2]):
                if ge <= gs:
                    continue
                best, label = 0, "none"
                # every span overlapping the gap starts in
                # [gs - longest, ge)
                i = bisect.bisect_left(starts, ge) - 1
                while i >= 0 and starts[i] >= gs - longest:
                    s = spans[i]
                    ov = min(s.end, ge) - max(s.start, gs)
                    if ov > best:
                        best, label = ov, s.name
                    i -= 1
                per[label] += (ge - gs) * 1e-9
        return [[k, v / max(len(self.devices), 1)] for k, v in
                sorted(per.items(), key=lambda kv: -kv[1])[:n]]


def _scope_of(ev) -> str:
    try:
        for k, v in ev.stats:
            if k in _SCOPE_STATS and isinstance(v, str):
                return v
    except (AttributeError, TypeError, ValueError):
        pass
    return ""


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, host_names: Sequence[str]) -> Tuple[DeviceTrace, dict]:
    """Read an ``.xplane.pb`` file.  Returns the trace and an inventory
    of its planes and lines (plane name -> {line name: events read})."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    wanted = set(host_names) | {WINDOW_SPAN}
    devices: List[DevicePlane] = []
    host: List[Interval] = []
    inventory: dict = {}
    for plane in pd.planes:
        lines = {}
        ops: List[Interval] = []
        mods: List[Interval] = []
        is_dev = plane.name.startswith("/device:") \
            and "CPU" not in plane.name
        for line in plane.lines:
            count, found = 0, False
            for ev in line.events:
                count += 1
                if is_dev:
                    if line.name not in (OPS_LINE, MODULES_LINE):
                        break
                    s = int(ev.start_ns)
                    iv = Interval(s, s + int(ev.duration_ns), ev.name)
                    if line.name == OPS_LINE:
                        iv.scope = _scope_of(ev)
                        ops.append(iv)
                    else:
                        mods.append(iv)
                elif ev.name in wanted:
                    found = True
                    s = int(ev.start_ns)
                    host.append(Interval(s, s + int(ev.duration_ns),
                                         ev.name))
                elif not found and count >= HOST_PEEK:
                    # a runtime thread's line: none of the benchmark's
                    # spans among its first events
                    break
            lines[line.name] = count
        inventory[plane.name] = lines
        if is_dev:
            mods.sort(key=lambda m: m.start)
            devices.append(DevicePlane(plane.name, ops, mods))
    windows = [h for h in host if h.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no '{WINDOW_SPAN}' host span in {path}; planes: "
                         f"{inventory}")
    w = max(windows, key=lambda h: h.dur)
    spans = [h for h in host if h.name != WINDOW_SPAN]
    return DeviceTrace(devices, spans, (w.start, w.end)), inventory


def least_time_s(work: Sequence[Tuple[float, float]], peaks: dict
                 ) -> Tuple[float, str]:
    """The least time a set of calls can take on this chip — each call
    bound by its operations over peak FLOP/s or its bytes over HBM
    bandwidth, whichever is larger — and which of the two binds the
    most of that time (``"flops"`` or ``"bytes"``)."""
    t_fl = t_by = 0.0
    total = 0.0
    for flops, nbytes in work:
        a = flops / peaks["bf16_flops_per_s"]
        b = nbytes / peaks["hbm_bytes_per_s"]
        total += max(a, b)
        if a >= b:
            t_fl += a
        else:
            t_by += b
    return total, ("flops" if t_fl > t_by else "bytes")


def roofline_share(work_per_pass: Sequence[Tuple[float, float]],
                   passes: int, kernel_s: float, peaks: dict
                   ) -> Optional[Tuple[float, str]]:
    """Percent of the roofline a kernel reached over ``passes`` passes,
    and the binding bound; ``None`` where the kernel did not run."""
    if kernel_s <= 0 or passes <= 0 or not work_per_pass:
        return None
    least, bound = least_time_s(work_per_pass, peaks)
    return 100.0 * least * passes / kernel_s, bound
