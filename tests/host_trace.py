"""The host events of a ``jax.profiler`` trace captured around a call,
for tests of the program's spans."""

from __future__ import annotations

import dataclasses
import glob

import jax


@dataclasses.dataclass
class HostEvent:
    start: float  # microseconds
    end: float
    name: str
    stats: dict

    def holds(self, other) -> bool:
        return self.start <= other.start and other.end <= self.end


def host_events(log_dir, fn, python_tracer: bool = False) -> list:
    """Run ``fn`` under a ``jax.profiler`` trace; the host events of the
    capture, sorted by start."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = int(python_tracer)
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                events.append(HostEvent(
                    e.start_ns * 1e-3, (e.start_ns + e.duration_ns) * 1e-3,
                    e.name,
                    dict(e.stats) if e.name.startswith("tpucfd.") else {}))
    return sorted(events, key=lambda e: (e.start, -e.end))
