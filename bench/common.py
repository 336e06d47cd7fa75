"""What ``run.py`` and the drivers share: the run's context and outcome."""

from __future__ import annotations

import dataclasses
import sys
import time

TRACE_SECONDS = 2.0          # the traced part of a --trace 1 window


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def key_for(seed: int):
    """A JAX key from any seed below 2**63."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, the run's options, the chips."""
    t_process: float
    cell: dict
    config: dict
    model: object
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    check: str | None
    devices: list
    marks: list[tuple[str, float]] = dataclasses.field(default_factory=list)

    @property
    def chips(self) -> int:
        return self.cell["chips"]

    def setup_done(self) -> float:
        """Seconds from process start to now: the cell's ``setup_s``."""
        return time.perf_counter() - self.t_process

    def mark(self, phase: str) -> None:
        """Note that ``phase`` has ended, on the host clock."""
        self.marks.append((phase, time.perf_counter() - self.t_process))

    def phases(self) -> str:
        """How long each marked phase took, for the run's log."""
        out, t = [], 0.0
        for phase, at in self.marks:
            out.append(f"{phase} {at - t:.3f}")
            t = at
        return "phases (s): " + ", ".join(out)

    @property
    def fault(self) -> str | None:
        return (self.check or "").partition("fault=")[2] or None


def profile_options():
    """Profiler options for a traced window: the device alone.  The host and
    Python tracers slowed a training step about four times over on a v5e;
    the benchmark keeps its own host spans (``trace.HostSpans``)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    return opts


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    end_to_end: dict[str, float]
    attempted: int
    failed: int
    checks: dict[str, float]           # number compared -> its reading
    memory_peak_bytes: int | None
    readings: object = None            # what the per-layer readers read
    notes: list[str] = dataclasses.field(default_factory=list)
