"""Capture a profiler trace of the window and reduce it to numbers.

The JAX profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
that ran, named by its HLO text (``%name = shape kind(operands) ...``).
A Pallas kernel is a ``custom-call`` whose name carries the jitted
function it was called from (``jvp_jit_flash_attention__.1``,
``transpose_jvp_jit_flash_attention___.3``, ...).  The harness's own
spans (``jax.profiler.TraceAnnotation`` around its calls into the program)
are events of the host plane.

The device's clock runs about a millisecond behind the host's on a v5e.
Each ``XLA Modules`` event on the device follows the host's
``PJRT_LoadedExecutable_Execute`` call that launched it, so where the two
counts match, device times are shifted by the largest lead of a launch
over its module; otherwise they are left as they are.

Everything below :func:`load` works on plain lists, so it is tested on a
small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH_EVENT = "PJRT_LoadedExecutable_Execute"


@dataclass
class Trace:
    # device index -> operations on it, by their HLO instruction name
    device_ops: Dict[int, List[Event]] = field(default_factory=dict)
    host_spans: List[Event] = field(default_factory=list)
    # HLO instruction name -> its kind ("fusion", "custom-call", ...)
    kinds: Dict[str, str] = field(default_factory=dict)
    clock_shift_ns: float = 0.0

    @property
    def window(self) -> Tuple[float, float]:
        wins = [(s, e) for n, s, e in self.host_spans if n == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        return wins[-1]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def ops_in_window(self, device: Optional[int] = None) -> List[Event]:
        lo, hi = self.window
        devs = sorted(self.device_ops) if device is None else [device]
        return [(n, max(s, lo), min(e, hi))
                for d in devs for n, s, e in self.device_ops[d]
                if e > lo and s < hi]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.device_ops:
            return 0.0
        tot = sum(union_ns(self.ops_in_window(d)) for d in self.device_ops)
        return tot / len(self.device_ops) * 1e-9

    def kernel_s(self, match: Sequence[str]) -> float:
        """Summed device seconds of the custom calls (Pallas kernels) whose
        name contains any of ``match``, over all devices."""
        return sum(e - s for n, s, e in self.ops_in_window()
                   if self.kinds.get(n) == "custom-call"
                   and any(m in n for m in match)) * 1e-9


def union_ns(events: Sequence[Event]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which no event runs."""
    out, t = [], lo
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


# control flow whose events enclose the operations of its body
CONTAINERS = ("while", "conditional", "call")


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time, summed by name (loops
    and calls, whose events enclose their bodies' operations, left out)."""
    tot: Dict[str, float] = {}
    for name, s, e in trace.ops_in_window():
        if trace.kinds.get(name) in CONTAINERS:
            continue
        tot[name] = tot.get(name, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{name} ({trace.kinds.get(name, '?')})", ns * 1e-9]
            for name, ns in ranked]


def idle_by_span(trace: Trace, n: int = 10) -> List[List]:
    """Idle device time, by the innermost harness span that covered it.

    Averaged over devices; idle time under no span but the window itself
    is charged to the window span."""
    lo, hi = trace.window
    segs = _innermost_segments(
        [sp for sp in trace.host_spans if sp[0] != WINDOW_SPAN
         and sp[2] > lo and sp[1] < hi], lo, hi)
    starts = [s for s, _, _ in segs]
    tot: Dict[str, float] = {}
    devs = sorted(trace.device_ops)
    for d in devs:
        for a, b in gaps(trace.ops_in_window(d), lo, hi):
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(segs) and segs[i][0] < b:
                s, e, name = segs[i]
                cut = min(e, b) - max(s, a)
                if cut > 0:
                    tot[name] = tot.get(name, 0.0) + cut
                i += 1
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / max(len(devs), 1)] for name, ns in ranked]


def _innermost_segments(spans: Sequence[Event], lo: float, hi: float
                        ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut at every span boundary, each piece named by the
    shortest span that covers it (the window span where none does)."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    by_start = sorted(spans, key=lambda sp: sp[1])
    active: List[Event] = []
    out, j = [], 0
    for x, y in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j][1] <= x:
            active.append(by_start[j])
            j += 1
        active = [sp for sp in active if sp[2] > x]
        name = (min(active, key=lambda sp: sp[2] - sp[1])[0] if active
                else WINDOW_SPAN)
        out.append((x, y, name))
    return out


_KIND = re.compile(r"[\]}) ]([a-z][\w-]*)\(")


def split_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, kind) of an ``XLA Ops`` event's HLO text."""
    name, _, rest = text.partition(" = ")
    m = _KIND.search(rest)
    return name.lstrip("%"), (m.group(1) if m else "?")


def clock_shift(modules: Sequence[float], launches: Sequence[float]
                ) -> float:
    """Nanoseconds to add to device times to put them on the host's
    clock: the largest lead of a launch over its module, where modules and
    launches pair up one to one; 0 where they do not."""
    if not modules or len(modules) != len(launches):
        return 0.0
    return max(0.0, max(h - d for d, h in zip(sorted(modules),
                                              sorted(launches))))


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    modules: List[float] = []
    launches: List[float] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = []
                    for ev in line.events:
                        name, kind = split_hlo(ev.name)
                        tr.kinds[name] = kind
                        ops.append((name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
                    tr.device_ops[idx] = ops
                elif line.name == MODULES_LINE and idx == 0:
                    modules = [ev.start_ns for ev in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        tr.host_spans.append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
                    elif ev.name == LAUNCH_EVENT:
                        launches.append(ev.start_ns)
    shift = clock_shift(modules, launches)
    tr.clock_shift_ns = shift
    tr.device_ops = {d: [(n, s + shift, e + shift) for n, s, e in ops]
                     for d, ops in tr.device_ops.items()}
    return tr


@contextlib.contextmanager
def capture() -> Iterator[Dict[str, Trace]]:
    """Trace the body into a temporary directory; the reduced trace is in
    ``box["trace"]`` after the block, and the files are gone."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    box: Dict[str, Trace] = {}
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield box
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        box["trace"] = load(max(files, key=os.path.getmtime))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
