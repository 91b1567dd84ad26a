"""One run of one cell: its arguments, set-up clock, window and readings.

A driver (``bench/drivers/<driver>.py``) receives a ``Session``, makes its
data, builds the system, warms it, calls ``setup_done()``, runs its window
inside ``with session.window():``, and returns an ``Outcome``. The session
keeps the set-up time, counts compiles in the window, and in a traced run
records the device trace and the program's tracer spans of the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from harness.clock import CompileClock
from harness.trace import DeviceTrace, Recorder


def log(msg: str) -> None:
    """An earlier line of the run's output (never the last)."""
    print(f"[bench] {msg}", flush=True)


@dataclasses.dataclass
class Readings:
    """What the per-layer readers (``bench/metrics/*.py``) read."""

    cell: str
    d: int
    peaks: object  # harness.peaks.Peaks
    passes: int = 0  # batch: whole passes of the log in the window
    spans: List[dict] = dataclasses.field(default_factory=list)  # program tracer events
    tracer_t0_ns: int = 0
    device: Optional[DeviceTrace] = None
    work: Optional[dict] = None  # harness.work.scan_work of one pass
    profile: Optional[dict] = None  # {phase/mode: KernelProfiler totals}

    def span_seconds(self, name: str) -> List[float]:
        return [ev["dur"] / 1e6 for ev in self.spans if ev.get("ph") == "X" and ev["name"] == name]


@dataclasses.dataclass
class Outcome:
    end_to_end: Dict[str, float]  # without setup_s, which the session adds
    numbers: Dict[str, float]  # compared against the cell's limits
    attempted: int
    failed: int
    memory_peak_bytes: int
    readings: Readings
    control_numbers: Optional[Dict[str, float]] = None


class Session:
    def __init__(self, cell, *, seed: int, seconds: float, trace: bool, devices,
                 peaks, t_start: float, out_dir: Path, control: bool = False) -> None:
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.peaks = peaks
        self.t_start = t_start
        self.out_dir = out_dir
        self.control = control
        self.clock = CompileClock()
        self.setup_s: Optional[float] = None
        self.setup_compiles: Optional[dict] = None
        self.window_compiles: Optional[int] = None
        self.window_loads: Optional[int] = None
        self.recorder = Recorder(str(out_dir / "profile")) if trace else None
        self.tracer = None
        self.profiler = None

    @contextlib.contextmanager
    def phase(self, name: str):
        c0 = self.clock.reading()
        t0 = time.perf_counter()
        yield
        c1 = self.clock.reading()
        log(
            f"{name}: {time.perf_counter() - t0:.3f} s; {c1['compiles'] - c0['compiles']} compiles, "
            f"{c1['compile_s'] - c0['compile_s']:.3f} s compiling, "
            f"{c1['saved_s'] - c0['saved_s']:.3f} s saved by the cache"
        )

    def setup_done(self) -> None:
        """Set-up ends here: the next operation is timed."""
        self.setup_s = time.perf_counter() - self.t_start
        self.setup_compiles = self.clock.reading()
        log(f"setup_s {self.setup_s:.3f}; set-up compiles {self.setup_compiles}")

    @contextlib.contextmanager
    def window(self):
        """The measured window. In a traced run the program's tracer and
        kernel profiler are on and the device is profiled."""
        assert self.setup_s is not None, "setup_done() first"
        if self.trace:
            from repro.obs import profile as obs_profile
            from repro.obs import trace as obs_trace

            self.tracer = obs_trace.enable(capacity=2_000_000)
            self.profiler = obs_profile.enable_profiler(hardware=_ProfilerPeaks(self.peaks))
        c0, l0 = self.clock.count, self.clock.loads
        try:
            if self.recorder is not None:
                with self.recorder.window():
                    yield
            else:
                yield
        finally:
            self.window_compiles = self.clock.count - c0
            self.window_loads = self.clock.loads - l0
            if self.trace:
                from repro.obs import profile as obs_profile
                from repro.obs import trace as obs_trace

                obs_trace.disable()
                obs_profile.disable_profiler()
        log(f"compiles in the window: {self.window_compiles}; "
            f"programs loaded from the compile cache: {self.window_loads}")

    def readings(self) -> Readings:
        r = Readings(cell=self.cell.name, d=int(self.cell.config["d"]), peaks=self.peaks)
        if self.trace:
            r.spans = self.tracer.events()
            r.tracer_t0_ns = self.tracer._t0_ns
            r.device = self.recorder.reduce()
            r.profile = {
                "scan": self.profiler.totals("scan", "f32"),
                "merge": self.profiler.totals("merge"),
            }
        return r


@dataclasses.dataclass(frozen=True)
class _ProfilerPeaks:
    """The program's kernel profiler wants peak terms; give it the
    benchmark's (its own fractions are not read)."""

    peaks: object

    @property
    def name(self) -> str:
        return self.peaks.kind

    @property
    def peak_flops(self) -> float:
        return self.peaks.peak_flops

    @property
    def hbm_bw(self) -> float:
        return self.peaks.hbm_bw

    @property
    def link_bw(self) -> float:
        return 0.0

    def as_dict(self) -> dict:
        return {"name": self.name, "peak_flops": self.peak_flops, "hbm_bw": self.hbm_bw}


def eprint(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
