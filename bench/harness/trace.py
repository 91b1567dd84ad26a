"""Reduce a ``jax.profiler`` trace of the measured window to device numbers.

The traced run wraps its window in one ``TraceAnnotation`` (``WINDOW``) and
notes the host's ``perf_counter_ns`` as it enters it; that pair puts the
program's tracer spans on the profiler's clock. From the ``.xplane.pb``:

* device ops: every event on the ``XLA Ops`` line of each ``/device:TPU:N``
  plane, with the HLO module it belongs to (the ``XLA Modules`` line);
* busy seconds: the union of a device's op intervals inside the window;
* idle gaps: the stretches of the window in which a device ran nothing,
  each named by the innermost program span that covers its middle.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import time
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction as the trace gives it: "%fused_knn.1 = (...) custom-call(...)"
    module: str  # "jit_fused_knn(2233347581744820355)"
    start_ns: float
    dur_ns: float
    device: int

    @property
    def short(self) -> str:
        """The instruction's name, "%fused_knn.1"."""
        return self.name.split(" = ", 1)[0]


@dataclasses.dataclass
class DeviceTrace:
    window: Tuple[float, float]  # profiler ns
    ops: List[Op]
    offset_ns: float  # profiler ns minus host perf_counter ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def devices(self) -> List[int]:
        return sorted({op.device for op in self.ops})

    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        """Merged [start, end) ns intervals in which ``device`` ran an op,
        clipped to the window."""
        w0, w1 = self.window
        spans = sorted(
            (max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1))
            for op in self.ops
            if op.device == device and op.start_ns < w1 and op.start_ns + op.dur_ns > w0
        )
        merged: List[List[float]] = []
        for s, e in spans:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        devs = self.devices()
        if not devs:
            return 0.0
        total = sum(e - s for d in devs for s, e in self.busy_intervals(d))
        return total / len(devs) / 1e9

    def op_seconds(self, match) -> float:
        """Device seconds (summed over devices) of ops for which
        ``match(op)`` holds, inside the window."""
        w0, w1 = self.window
        total = 0.0
        for op in self.ops:
            if match(op):
                s, e = max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1)
                total += max(0.0, e - s)
        return total / 1e9

    def top_ops(self, n: int = 10) -> List[List]:
        """[[module/op, seconds], ...]: the ops that took most device time."""
        acc: Dict[str, float] = {}
        for op in self.ops:
            module = op.module.split("(", 1)[0]
            key = f"{module}/{op.short}" if module else op.short
            acc[key] = acc.get(key, 0.0) + op.dur_ns / 1e9
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, spans: List[dict], t0_ns: int, n: int = 10) -> List[List]:
        """[[host span, idle seconds], ...]: the device's idle time in the
        window, summed by the innermost program span covering each gap's
        middle (``spans`` are tracer events, ``t0_ns`` the tracer's epoch)."""
        devs = self.devices()
        if not devs:
            return []
        w0, w1 = self.window
        host = sorted(
            (
                t0_ns + ev["ts"] * 1e3 + self.offset_ns,
                t0_ns + (ev["ts"] + ev["dur"]) * 1e3 + self.offset_ns,
                ev["name"],
            )
            for ev in spans
            if ev.get("ph") == "X"
        )
        starts = [h[0] for h in host]
        acc: Dict[str, float] = {}
        prev = w0
        for s, e in self.busy_intervals(devs[0]) + [(w1, w1)]:
            if s > prev:
                mid = (prev + s) / 2
                i = bisect.bisect_right(starts, mid)
                name, best = "no span", None
                for h0, h1, hname in host[max(0, i - 256): i]:
                    if h0 <= mid < h1 and (best is None or h1 - h0 < best):
                        name, best = hname, h1 - h0
                acc[name] = acc.get(name, 0.0) + (s - prev) / 1e9
            prev = max(prev, e)
        return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


class Recorder:
    """Profiles one window: ``with rec.window(): ...``, then ``rec.reduce()``."""

    def __init__(self, out_dir: str) -> None:
        self.out_dir = out_dir
        self.enter_perf_ns: Optional[int] = None

    @contextlib.contextmanager
    def window(self):
        import jax

        os.makedirs(self.out_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the program's own spans name the host's time
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        try:
            self.enter_perf_ns = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return found[-1]

    def reduce(self) -> DeviceTrace:
        return reduce_xplane(self.xplane(), self.enter_perf_ns)


def reduce_xplane(path: str, enter_perf_ns: Optional[int]) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    ops: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            tail = plane.name[len("/device:TPU:"):]
            if not tail.isdigit():
                continue  # e.g. a SparseCore plane
            device = int(tail)
            modules = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted((ev.start_ns, ev.end_ns, ev.name) for ev in line.events)
            mstarts = [m[0] for m in modules]
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    i = bisect.bisect_right(mstarts, ev.start_ns) - 1
                    module = ""
                    if i >= 0 and modules[i][1] >= ev.start_ns:
                        module = modules[i][2]
                    ops.append(Op(ev.name, module, float(ev.start_ns), float(ev.duration_ns), device))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (float(ev.start_ns), float(ev.end_ns))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    offset = window[0] - enter_perf_ns if enter_perf_ns is not None else 0.0
    return DeviceTrace(window=window, ops=ops, offset_ns=offset)


def describe(path: str, max_names: int = 40) -> dict:
    """Planes, lines, event counts and the most frequent event names: what a
    person reads before trusting the reduction."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            names: Dict[str, List[float]] = {}
            stats = None
            for ev in line.events:
                acc = names.setdefault(ev.name, [0, 0.0])
                acc[0] += 1
                acc[1] += ev.duration_ns / 1e9
                if stats is None:
                    stats = [[k, str(v)[:80]] for k, v in ev.stats]
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:max_names]
            lines.append({"line": line.name, "events": sum(v[0] for v in names.values()),
                          "top": top, "first_stats": stats})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}
