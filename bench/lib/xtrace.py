"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
:func:`load` reads it with ``jax.profiler.ProfileData`` into plain
:class:`Event` lists: the device's operations (the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane; on a CPU-only trace, the host events that carry
an ``hlo_op`` stat) and the host's annotated activity.

Everything else here works on those lists, so the tests can build a trace
by hand:

- busy time: the union of operation intervals on a device, clipped to the
  window, averaged over devices;
- kernel time: the summed durations of the operations whose name or
  metadata matches a kernel's patterns (a TPU trace names each operation
  by its whole HLO instruction, ``%_bin_full_full.13 = f32[...]
  custom-call(...), custom_call_target="tpu_custom_call", ...``);
- idle gaps: the stretches of the window with no operation on device 0,
  each named by the innermost host event around its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the host annotation that brackets the measured window
WINDOW = "bench:window"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    meta: str = ""               # the event's string stats, joined
    device: int = 0

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def matches(self, patterns: Sequence[str]) -> bool:
        text = f"{self.name} {self.meta}"
        return any(re.search(p, text) for p in patterns)


@dataclasses.dataclass
class Trace:
    device_events: List[Event]
    host_events: List[Event]
    window: Tuple[float, float]  # ns, on the trace's clock
    n_devices: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, events: Iterable[Event]) -> List[Tuple[float, float]]:
        lo, hi = self.window
        out = []
        for e in events:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                out.append((s, t))
        return out

    def busy_intervals(self, device: int = 0) -> List[Tuple[float, float]]:
        """The union of operation intervals on ``device``, in the window."""
        spans = sorted(self._clipped(e for e in self.device_events
                                     if e.device == device))
        merged: List[List[float]] = []
        for s, t in spans:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t)
            else:
                merged.append([s, t])
        return [(s, t) for s, t in merged]

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        tot = sum(t - s for d in range(self.n_devices)
                  for s, t in self.busy_intervals(d))
        return tot * 1e-9 / max(self.n_devices, 1)

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def kernel_events(self, patterns: Sequence[str]) -> List[Event]:
        lo, hi = self.window
        return [e for e in self.device_events
                if e.matches(patterns) and e.end_ns > lo and e.start_ns < hi]

    def kernel_s(self, patterns: Sequence[str]) -> float:
        """Summed device seconds of the matching operations, per device."""
        return sum(t - s for s, t in self._clipped(
            self.kernel_events(patterns))) * 1e-9 / max(self.n_devices, 1)

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """Device seconds per operation, leaves only (a loop's event
        encloses its body's events on the same line)."""
        lo, hi = self.window
        tot: Dict[str, float] = defaultdict(float)
        for e in leaves(self.device_events):
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                tot[op_label(e)] += d * 1e-9 / max(self.n_devices, 1)
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]

    def gaps(self, device: int = 0) -> List[Tuple[float, float]]:
        lo, hi = self.window
        out, cur = [], lo
        for s, t in self.busy_intervals(device):
            if s > cur:
                out.append((cur, s))
            cur = max(cur, t)
        if hi > cur:
            out.append((cur, hi))
        return out

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """Idle seconds on device 0 by what the host was doing meanwhile:
        each gap goes to the shortest host event that contains its
        midpoint (one sweep over both, sorted)."""
        host = sorted((e for e in self.host_events if e.name != WINDOW),
                      key=lambda e: e.start_ns)
        gaps = sorted(self.gaps(0), key=lambda g: g[0] + g[1])
        tot: Dict[str, float] = defaultdict(float)
        active: List[Tuple[float, int, Event]] = []   # (duration, i, event)
        i = 0
        for s, t in gaps:
            mid = 0.5 * (s + t)
            while i < len(host) and host[i].start_ns <= mid:
                heapq.heappush(active, (host[i].dur_ns, i, host[i]))
                i += 1
            # events are taken in start order and the midpoints rise, so
            # an event that has ended before this one never covers a later
            while active and active[0][2].end_ns < mid:
                heapq.heappop(active)
            tot[active[0][2].name if active else "host idle"] += (t - s) * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


_HLO = re.compile(r"^%?([\w.-]+) = (\S+)")
_INSTANCE = re.compile(r"(\.(\d+|clone|sunk))+$")
_LAYOUT = re.compile(r"\{[^}]*\}")


def op_label(e: Event) -> str:
    """A device operation's name without its instance number, with its
    result type when that is one array (``fusion f32[131072,32]``). TPU
    traces name each operation by its whole HLO instruction."""
    m = _HLO.match(e.name)
    if not m:
        return _INSTANCE.sub("", e.name)
    name, result = _INSTANCE.sub("", m.group(1)), m.group(2)
    if result.startswith("("):
        return name
    return f"{name} {_LAYOUT.sub('', result)}"


def leaves(events: List[Event]) -> List[Event]:
    """The events that enclose no other event of their device."""
    out = []
    by_dev: Dict[int, List[Event]] = defaultdict(list)
    for e in events:
        by_dev[e.device].append(e)
    for evs in by_dev.values():
        evs = sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns))
        for i, e in enumerate(evs):
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is None or nxt.start_ns >= e.end_ns:
                out.append(e)
    return out


# -- loading -----------------------------------------------------------------

def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats_text(ev) -> str:
    parts = []
    try:
        for k, v in ev.stats:
            if isinstance(v, str):
                parts.append(f"{k}={v}")
    except Exception:            # noqa: BLE001 — stats are optional
        pass
    return " ".join(parts)


def load(log_dir: str) -> Trace:
    """Read the newest trace under ``log_dir`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(log_dir))
    device_events: List[Event] = []
    host_events: List[Event] = []
    cpu_ops: List[Event] = []
    n_dev = 0
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            n_dev = max(n_dev, dev + 1)
            for ev in line.events:
                device_events.append(Event(ev.name, ev.start_ns,
                                           ev.duration_ns, _stats_text(ev),
                                           dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    meta = _stats_text(ev)
                    e = Event(ev.name, ev.start_ns, ev.duration_ns, meta)
                    if "hlo_op=" in meta:
                        cpu_ops.append(e)
                    elif ev.duration_ns > 0:
                        host_events.append(e)
    if not device_events:            # a CPU-only trace: host-executed ops
        device_events, n_dev = cpu_ops, 1
    windows = [e for e in host_events if e.name == WINDOW]
    if windows:
        w = max(windows, key=lambda e: e.dur_ns)
        window = (w.start_ns, w.end_ns)
    elif device_events:
        window = (min(e.start_ns for e in device_events),
                  max(e.end_ns for e in device_events))
    else:
        window = (0.0, 0.0)
    return Trace(device_events, host_events, window, max(n_dev, 1))


def describe(log_dir: str, max_events: int = 8) -> str:
    """Planes, lines and a few events of a trace, for reading it by hand."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(log_dir))
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:max_events]:
                out.append(f"    {ev.name!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {_stats_text(ev)[:400]}")
    return "\n".join(out)
