"""Reduce a profiler trace (``.xplane.pb``) to device busy time and ops.

What a TPU trace holds (read by hand on a v5e, jax 0.9): each chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed
HLO instruction, named by the instruction's text
(``%name = bf16[...] opcode(operands...)``), one at a time on the core.
Async copies sit on another line and overlap it; they are not counted as
busy.  Event times are nanoseconds from the session's start, which the
plane ``Task Environment`` gives as ``profile_start_time`` on the epoch
clock.  The benchmark traces the device alone: the host tracer (which
records the runtime's and ``TraceAnnotation``'s events) slowed a training
step about four times over on a v5e.  So the benchmark keeps its own host
spans (``HostSpans``, on the epoch clock) and ``load`` moves them onto the
trace's clock.

Kinds of device op, by the instruction text:
  * ``kernel``     -- conv/deconv work: Pallas calls (``custom-call`` to
                      ``tpu_custom_call``; XLA's own custom calls, such as
                      ``ConcatBitcast``, are glue) and XLA convolutions;
  * ``collective`` -- ``all-reduce``/``all-gather``/``reduce-scatter``/
                      ``collective-permute`` and int8 gradient packing
                      (instructions that produce or read ``s8``/``s32``
                      tensors outside a kernel);
  * ``glue``       -- everything else: pads, phase split and interleave
                      copies, concats, the dense projection, the update.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TASK_PLANE = "Task Environment"
_COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"\)?\s*([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class Op:
    name: str          # the instruction's text
    start_ns: float
    dur_ns: float

    @property
    def short(self) -> str:
        """``%id opcode result-shape`` -- short and stable within a run."""
        ident, _, rest = self.name.partition(" = ")
        m = _OPCODE.search(rest)
        opcode = m.group(1) if m else "?"
        shape = rest.split(" ", 1)[0] if rest else ""
        return f"{ident} {opcode} {shape[:48]}"

    @property
    def kind(self) -> str:
        text = self.name
        _, _, rest = text.partition(" = ")
        m = _OPCODE.search(rest)
        opcode = m.group(1) if m else ""
        if (opcode == "custom-call" and PALLAS_TARGET in text) \
                or "convolution" in opcode:
            return "kernel"
        if any(c in opcode for c in _COLLECTIVE):
            return "collective"
        if "s8[" in rest or "s32[" in rest.split("(", 1)[0] \
                or re.search(r"s32\[\d+,", rest):
            return "collective"
        return "glue"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Op]]       # chip id -> ops in start order
    modules: dict[int, list[Op]]       # chip id -> executed programs
    spans: list[Span]                  # the benchmark's host spans
    t0_ns: float                       # earliest event of the trace
    t1_ns: float

    def ops_in(self, dev: int, lo: float, hi: float) -> list[Op]:
        return [o for o in self.devices[dev]
                if o.start_ns >= lo and o.start_ns + o.dur_ns <= hi]


def load(path: str, host_spans=()) -> Trace:
    """Read one ``.xplane.pb`` (a file, or a directory searched for one),
    with the benchmark's ``(name, start, end)`` host spans in epoch
    nanoseconds."""
    from jax.profiler import ProfileData
    if not path.endswith(".xplane.pb"):
        found = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    devices: dict[int, list[Op]] = {}
    modules: dict[int, list[Op]] = {}
    start_epoch_ns = 0
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                into = {OPS_LINE: ops, MODULES_LINE: mods}.get(line.name)
                if into is not None:
                    into.extend(Op(e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
            dev = int(m.group(1))
            devices[dev] = sorted(ops, key=lambda o: o.start_ns)
            modules[dev] = sorted(mods, key=lambda o: o.start_ns)
        elif plane.name == TASK_PLANE:
            start_epoch_ns = int(dict(plane.stats).get("profile_start_time",
                                                       0))
    spans = [Span(name, a - start_epoch_ns, b - start_epoch_ns)
             for name, a, b in host_spans]
    stamps = [o.start_ns for ops in devices.values() for o in ops] + \
        [s.start_ns for s in spans]
    ends = [o.start_ns + o.dur_ns for ops in devices.values() for o in ops] \
        + [s.end_ns for s in spans]
    return Trace(devices, modules, sorted(spans, key=lambda s: s.start_ns),
                 min(stamps, default=0.0), max(ends, default=0.0))


def union_ns(intervals, lo: float | None = None,
             hi: float | None = None) -> float:
    """Length of the union of ``(start, end)`` intervals, clipped."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(ops: list[Op], lo=None, hi=None) -> float:
    return union_ns([(o.start_ns, o.start_ns + o.dur_ns) for o in ops], lo, hi)


def overlap_ns(ops: list[Op], windows: list[tuple[float, float]]) -> float:
    """Device busy time of ``ops`` inside the union of ``windows``: one
    sweep over the two merged, sorted interval lists."""
    busy = _merge([(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
    total, i = 0.0, 0
    for lo, hi in _merge(windows):
        while i < len(busy) and busy[i][1] <= lo:
            i += 1
        j = i
        while j < len(busy) and busy[j][0] < hi:
            total += min(hi, busy[j][1]) - max(lo, busy[j][0])
            j += 1
    return total


def _merge(windows):
    out = []
    for s, e in sorted(windows):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def time_by_kind(ops: list[Op]) -> dict[str, float]:
    out = {"kernel": 0.0, "collective": 0.0, "glue": 0.0}
    for o in ops:
        out[o.kind] += o.dur_ns
    return out


def top_ops(ops: list[Op], n: int = 10) -> list[list]:
    """The ``n`` instructions that took most device time, in seconds."""
    tot: dict[str, float] = {}
    for o in ops:
        tot[o.short] = tot.get(o.short, 0.0) + o.dur_ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_gaps(ops: list[Op], spans: list[Span], lo: float, hi: float,
              n: int = 10) -> list[list]:
    """The ``n`` longest device idle gaps in [lo, hi], each named by the
    innermost benchmark span open on the host at the gap's midpoint."""
    gaps, cur = [], lo
    for s, e in _merge([(o.start_ns, o.start_ns + o.dur_ns) for o in ops]):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    by_start = sorted(spans, key=lambda sp: sp.start_ns)
    starts = [sp.start_ns for sp in by_start]
    longest = max((sp.end_ns - sp.start_ns for sp in spans), default=0)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        if e <= s:
            continue
        mid = (s + e) / 2
        first = bisect.bisect_left(starts, mid - longest)
        last = bisect.bisect_right(starts, mid)
        open_ = [sp for sp in by_start[first:last] if sp.end_ns >= mid]
        who = (min(open_, key=lambda sp: sp.end_ns - sp.start_ns).name
               if open_ else "no benchmark span")
        named.append([who, (e - s) / 1e9])
    return named


class HostSpans:
    """The benchmark's own spans around its calls into the program, kept
    while ``on`` as ``(name, start, end)`` in epoch nanoseconds."""

    def __init__(self):
        self.on = False
        self.kept: list[tuple[str, int, int]] = []

    def __call__(self, name: str) -> "_Span":
        return _Span(self, name)


class _Span:
    __slots__ = ("log", "name", "t0")

    def __init__(self, log: HostSpans, name: str):
        self.log, self.name = log, name

    def __enter__(self):
        self.t0 = time.time_ns() if self.log.on else None

    def __exit__(self, *exc):
        if self.t0 is not None:
            self.log.kept.append((self.name, self.t0, time.time_ns()))
