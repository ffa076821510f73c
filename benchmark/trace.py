"""Reduction of a JAX profiler trace (``.xplane.pb``) to what metrics read.

Read with nothing but JAX (``jax.profiler.ProfileData``).  What a v5e
trace holds (looked at by hand, PR 23): one plane ``/device:TPU:<n>`` a
chip, whose line ``XLA Ops`` has one event for every operation the core
ran (the event's name is the HLO instruction's text, ``%fusion.12 = ...``;
a Pallas kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``), whose line ``XLA Modules`` has
one event a program run, and one plane ``/host:CPU`` with a line a host
thread, where ``jax.profiler.TraceAnnotation`` spans appear under their
own names.  Device and host events share one clock, in nanoseconds.

- busy: the union of the ``XLA Ops`` intervals inside the window;
- idle share: 1 - busy / window;
- kernel time: the summed durations of the events a pattern matches;
- gaps: the idle intervals, each attributed to the host span that its
  middle fell in (or to "between" those spans).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def newest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def union_seconds(starts, ends) -> float:
    """Length of the union of the intervals, in the unit of the inputs."""
    return float(sum(b - a for a, b in merge(starts, ends)))


def merge(starts, ends) -> list:
    """The intervals merged where they touch or overlap, in order."""
    order = np.argsort(np.asarray(starts, dtype=np.float64), kind="stable")
    out = []
    for i in order:
        a, b = float(starts[i]), float(ends[i])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def short_name(text: str) -> str:
    """``%fusion.12 = bf16[8,128]{...} fusion(...)`` -> ``fusion
    bf16[8,128]``: the instruction's name without its number, with the
    first shape of its result, so that the same operation of every layer
    falls under one name."""
    head, _, rest = text.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    shape = _SHAPE.search(rest)
    return f"{base} {shape.group(0)}" if shape else base


@dataclasses.dataclass
class DeviceOps:
    index: int
    names: list            # distinct event texts
    name_id: np.ndarray    # per event, index into names
    start: np.ndarray      # ns
    end: np.ndarray        # ns


@dataclasses.dataclass
class Reduced:
    t0: float                       # window, ns on the trace's clock
    t1: float
    devices: list                   # DeviceOps, events clipped to the window
    host_spans: dict                # name -> [(start, end)] ns, any thread

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_seconds(d.start, d.end)
                   for d in self.devices) / len(self.devices) / 1e9

    @property
    def idle_share(self):
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, all_of=(), any_of=()) -> tuple:
        """(seconds, events) of the device events whose text holds every
        string of ``all_of`` and, if given, matches a regex of ``any_of``:
        summed over the chips' events and divided by the chips."""
        any_re = [re.compile(p) for p in any_of]
        total, count = 0.0, 0
        for d in self.devices:
            hit = np.array([all(s in n for s in all_of)
                            and (not any_re or
                                 any(r.search(n) for r in any_re))
                            for n in d.names], dtype=bool)
            sel = hit[d.name_id] if len(d.name_id) else hit[:0]
            total += float((d.end[sel] - d.start[sel]).sum())
            count += int(sel.sum())
        n = max(len(self.devices), 1)
        return total / n / 1e9, count

    def top_ops(self, n: int = 10) -> list:
        """[[short name, seconds]] of the operations that took most device
        time (first chip)."""
        if not self.devices:
            return []
        d = self.devices[0]
        per = {}
        dur = d.end - d.start
        sums = np.bincount(d.name_id, weights=dur, minlength=len(d.names))
        for text, s in zip(d.names, sums):
            key = short_name(text)
            per[key] = per.get(key, 0.0) + float(s)
        ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in ranked]

    def idle_gaps(self, span_names=(), n: int = 10) -> list:
        """[[where, seconds]]: the first chip's idle time inside the
        window, by what the host was doing: inside a span of one of
        ``span_names`` (the gap's middle falls in it) or between them."""
        if not self.devices:
            return []
        d = self.devices[0]
        busy = merge(d.start, d.end)
        edges = [self.t0] + [x for ab in busy for x in ab] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted((a, b, name) for name in span_names
                       for a, b in self.host_spans.get(name, ()))
        starts = np.array([s[0] for s in spans], dtype=np.float64)
        per = {}
        for a, b in gaps:
            mid = (a + b) / 2
            where = "between " + "/".join(span_names) if span_names \
                else "idle"
            i = int(np.searchsorted(starts, mid, side="right")) - 1
            # spans of one thread do not nest here; look a few back
            for j in range(i, max(i - 4, -1), -1):
                if j >= 0 and spans[j][0] <= mid <= spans[j][1]:
                    where = "inside " + spans[j][2]
                    break
            per[where] = per.get(where, 0.0) + (b - a)
        ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in ranked]


def reduce_trace(path: str, *, window_span: str = WINDOW_SPAN,
                 window_from: str = None,
                 ops_line: str = OPS_LINE) -> Reduced:
    """Read ``path`` (an ``.xplane.pb`` or a directory holding one) and cut
    it to the window: from the first start to the last end of the host
    spans named ``window_from`` if given, else the ``window_span`` host
    span if the trace has one, else from the first to the last device
    event."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = newest_xplane(path)
    data = ProfileData.from_file(path)
    host_spans, raw = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != ops_line:
                    continue
                ids, names, start, dur = {}, [], [], []
                nid = []
                for ev in line.events:
                    i = ids.get(ev.name)
                    if i is None:
                        i = ids[ev.name] = len(names)
                        names.append(ev.name)
                    nid.append(i)
                    start.append(ev.start_ns)
                    dur.append(ev.duration_ns)
                start = np.asarray(start, dtype=np.float64)
                raw.append(DeviceOps(int(m.group(1)), names,
                                     np.asarray(nid, dtype=np.int64), start,
                                     start + np.asarray(dur,
                                                        dtype=np.float64)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.setdefault(ev.name, []).append(
                            (float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns)))
    raw.sort(key=lambda d: d.index)
    if window_from and host_spans.get(window_from):
        t0 = min(a for a, _ in host_spans[window_from])
        t1 = max(b for _, b in host_spans[window_from])
    elif host_spans.get(window_span):
        t0, t1 = host_spans[window_span][0]
    elif any(len(d.start) for d in raw):
        t0 = min(float(d.start.min()) for d in raw if len(d.start))
        t1 = max(float(d.end.max()) for d in raw if len(d.start))
    else:
        t0 = t1 = 0.0
    devices = []
    for d in raw:
        keep = (d.end > t0) & (d.start < t1)
        devices.append(DeviceOps(d.index, d.names, d.name_id[keep],
                                 np.clip(d.start[keep], t0, t1),
                                 np.clip(d.end[keep], t0, t1)))
    for spans in host_spans.values():
        spans.sort()
    return Reduced(t0, t1, devices, host_spans)
