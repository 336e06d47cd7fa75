"""What the per-layer readers read: the traced window reduced once.

A driver records a trace of part of its window (``--trace 1``), notes which
calls into the program ran in it, and hands this object to every reader in
``bench/metrics/``.
"""

from __future__ import annotations

import dataclasses
import statistics

import flops as F
import trace as T


@dataclasses.dataclass
class Readings:
    kind: str                          # "serve"; the metric's suffix
    trace: T.Trace
    lo_ns: float                       # the traced window
    hi_ns: float
    windows: list[tuple[float, float]]  # where the device is judged idle
    passes: list[tuple[F.Layer, str, int]]  # conv/deconv calls per chip
    useful_flops: float                # model work done in the window
    chips: int
    peaks: dict
    counters: dict = dataclasses.field(default_factory=dict)

    def ops(self, dev: int) -> list[T.Op]:
        return self.trace.ops_in(dev, self.lo_ns, self.hi_ns)

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    @property
    def judged_s(self) -> float:
        """Length of the windows the idle share is judged over."""
        return T.union_ns(self.windows, self.lo_ns, self.hi_ns) / 1e9

    def mean_over_chips(self, fn) -> float:
        devs = sorted(self.trace.devices)
        return statistics.fmean(fn(d) for d in devs) if devs else 0.0

    @property
    def busy_s(self) -> float:
        return self.mean_over_chips(
            lambda d: T.busy_ns(self.ops(d), self.lo_ns, self.hi_ns)) / 1e9

    def busy_in_windows_s(self) -> float:
        return self.mean_over_chips(
            lambda d: T.overlap_ns(self.ops(d), self.windows)) / 1e9

    def kind_s(self, kind: str) -> float:
        return self.mean_over_chips(
            lambda d: T.time_by_kind(self.ops(d))[kind]) / 1e9

    @property
    def breakdown(self) -> dict:
        if not self.trace.devices:
            return {"device_ops": [], "idle_gaps": []}
        ops = self.ops(min(self.trace.devices))
        return {"device_ops": T.top_ops(ops),
                "idle_gaps": T.idle_gaps(ops, self.trace.spans, self.lo_ns,
                                         self.hi_ns)}
