"""From a profiler trace to the benchmark's numbers.

A traced run writes one ``.xplane.pb``. ``load`` reads it with
``jax.profiler.ProfileData`` and keeps three things, all on the trace's own
clock in nanoseconds:

* the device's operations (TPU: the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane; CPU, for tests: the XLA client threads' op
  events), per device;
* the device's programs (TPU: the ``XLA Modules`` line), per device;
* the host spans the benchmark opened (``chipbench.spans``), with the
  window span that bounds the traced part of the run.

The reductions below take only these lists, so each is tested on events
made by hand as well as on a trace recorded on the CPU.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from chipbench.spans import SPAN_PREFIXES, WINDOW_SPAN

# an idle stretch of the device shorter than this is dispatch, not a gap
GAP_MIN_NS = 1_000_000
# CPU client events that are bookkeeping of the thread pool, not operations
_CPU_SKIP = ("ThreadpoolListener", "ThunkExecutor", "end: ")


@dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float            # ns
    detail: str = ""      # the operation's long name or category, if any

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)        # device id -> [Event]
    programs: dict = field(default_factory=dict)   # device id -> [Event]
    spans: list = field(default_factory=list)      # [Event]
    window: tuple = (0.0, 0.0)                     # ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def find_xplane(out_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(out_dir), "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return paths[-1]


def _stat(ev, names) -> str:
    try:
        stats = dict(ev.stats)
    except Exception:       # noqa: BLE001 - stats decoding is best-effort
        return ""
    for n in names:
        if n in stats and stats[n]:
            return str(stats[n])
    return ""


def load(out_dir, platform: str) -> Trace:
    """Read the newest trace under ``out_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(out_dir))
    tr = Trace()
    for plane in pd.planes:
        if platform == "tpu" and plane.name.startswith("/device:TPU:") \
                and plane.name[len("/device:TPU:"):].isdigit():
            dev = int(plane.name[len("/device:TPU:"):])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[dev] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                              _stat(e, ("long_name", "hlo_category")))
                        for e in line.events]
                elif line.name == "XLA Modules":
                    tr.programs[dev] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                ev = Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith(SPAN_PREFIXES):
                    tr.spans.append(ev)
                elif (platform == "cpu"
                      and line.name.startswith("tf_XLAPjRtCpuClient")
                      and not e.name.startswith(_CPU_SKIP)
                      and e.duration_ns > 0):
                    tr.ops.setdefault(0, []).append(ev)
    windows = tr.spans_named(WINDOW_SPAN)
    if windows:
        tr.window = (windows[0].start, windows[0].end)
    for evs in list(tr.ops.values()) + list(tr.programs.values()):
        evs.sort(key=lambda e: e.start)
    tr.spans.sort(key=lambda e: e.start)
    return tr


def union(events, lo: float, hi: float) -> list:
    """The union of the events' intervals clipped to [lo, hi], merged."""
    out: list = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(t - s for s, t in union(events, lo, hi))


def busy_s(tr: Trace, lo: float | None = None, hi: float | None = None
           ) -> float:
    """Seconds in which some operation ran on a device, averaged over the
    devices, within [lo, hi] (default: the window)."""
    lo = tr.window[0] if lo is None else lo
    hi = tr.window[1] if hi is None else hi
    if not tr.ops:
        return 0.0
    return sum(busy_ns(evs, lo, hi) for evs in tr.ops.values()) \
        / len(tr.ops) * 1e-9


def busy_within(tr: Trace, spans) -> tuple:
    """(busy seconds, span seconds) of the device inside ``spans``."""
    busy = sum(busy_s(tr, s.start, s.end) for s in spans)
    return busy, sum(s.seconds for s in spans)


def gaps(events, lo: float, hi: float, min_ns: float = GAP_MIN_NS) -> list:
    """The idle stretches of a device within [lo, hi], at least ``min_ns``
    long, as (start, end)."""
    out, t = [], lo
    for s, e in union(events, lo, hi):
        if s - t >= min_ns:
            out.append((t, s))
        t = e
    if hi - t >= min_ns:
        out.append((t, hi))
    return out


def innermost(spans, t: float) -> str:
    """The name of the latest-started span open at ``t`` other than the
    window span; "none" where only the window is open."""
    best = None
    for s in spans:
        if s.start > t:
            break
        if s.end >= t and s.name != WINDOW_SPAN:
            if best is None or s.start >= best.start:
                best = s
    return best.name if best else "none"


def idle_by_span(tr: Trace, limit: int = 10) -> list:
    """Idle gaps of device 0 in the window, summed by the host span open at
    the middle of each: [[span, seconds], ...], longest first."""
    if not tr.ops:
        return []
    dev = min(tr.ops)
    acc: dict = defaultdict(float)
    for s, e in gaps(tr.ops[dev], *tr.window):
        acc[innermost(tr.spans, (s + e) / 2)] += (e - s) * 1e-9
    return sorted(([k, v] for k, v in acc.items()),
                  key=lambda kv: -kv[1])[:limit]


def short_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3 fusion``: a TPU
    trace names each operation by its whole HLO instruction."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def top_ops(tr: Trace, limit: int = 10) -> list:
    """Device operations in the window by total time on device 0:
    [[name, seconds], ...]."""
    if not tr.ops:
        return []
    dev = min(tr.ops)
    acc: dict = defaultdict(float)
    lo, hi = tr.window
    for e in tr.ops[dev]:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            acc[short_name(e.name)] += (t - s) * 1e-9
    return sorted(([k, v] for k, v in acc.items()),
                  key=lambda kv: -kv[1])[:limit]


def is_kernel(e: Event) -> bool:
    """A Pallas kernel: on the TPU one ``tpu_custom_call``, which the trace
    names as a custom call."""
    text = f"{e.name} {e.detail}".lower()
    return "custom-call" in text or "custom_call" in text

