"""The program's own host spans in a profiler trace, and the device's idle
time charged to them.

``Trainer.run_step`` records ``train.step`` around ``train.plan``,
``train.batch``, ``train.launch``, ``train.wait`` and ``train.observe``
(``jax.profiler.TraceAnnotation``, on the same host clock as the
harness's ``bench.*`` spans).  :func:`load` reads them from an
``.xplane.pb``; :func:`idle_by_span` charges each idle stretch of the
device in the window to the innermost span among the harness's and the
program's, with :func:`bench.harness.trace.idle_by_span`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional

from bench.harness import trace as trace_mod
from bench.harness.trace import Event, Trace

PREFIX = "train."


@dataclass
class ProgramSpans:
    spans: List[Event]
    # the device's modules and the host's launches pair up one to one, so
    # ``trace.load`` could put the device's times on the host's clock
    # (with a shift of 0 where no launch led its module)
    paired: bool


def load(path: str) -> ProgramSpans:
    """The ``train.*`` events of the host planes of one ``.xplane.pb``,
    and whether its modules and launches pair up."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans: List[Event] = []
    modules = launches = 0
    for plane in data.planes:
        for line in plane.lines:
            if (plane.name.split()[0] == trace_mod.DEVICE_PREFIX + "0"
                    and line.name == trace_mod.MODULES_LINE):
                modules += sum(1 for _ in line.events)
            elif plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name == trace_mod.LAUNCH_EVENT:
                        launches += 1
    return ProgramSpans(sorted(spans, key=lambda sp: sp[1]),
                        paired=0 < modules == launches)


def idle_by_span(trace: Trace, program: ProgramSpans
                 ) -> Optional[Dict[str, float]]:
    """Idle device seconds in the window by the innermost span, harness's
    or program's, that covered them, averaged over devices.  None where the
    device's clock could not be put on the host's (modules and launches do
    not pair: the ~1.3 ms the v5e's device clock can run behind would
    charge idle time to the wrong span) or the program recorded no span."""
    if not program.paired or not program.spans:
        return None
    both = dataclasses.replace(
        trace, host_spans=list(trace.host_spans) + program.spans)
    return dict(trace_mod.idle_by_span(both, n=len(both.host_spans) + 1))
