"""Reduction of a profiler trace (``*.xplane.pb``) to device busy time,
per-operation time and idle gaps tagged by the benchmark's host spans.

Device events are those of the planes named ``/device:...``.  On a TPU the
line ``XLA Modules`` holds one event per executed program and ``XLA Ops``
one per HLO operation in it; busy time is the union of both lines'
intervals inside the window (so an operation the profiler dropped still
counts through its program), averaged over the device planes; the
per-operation times come from ``XLA Ops`` and the per-program times, one
entry per program name, from ``XLA Modules``.  A plane without those lines
(the CPU's) is read whole.  The window and the host spans are the
benchmark's own ``TraceAnnotation`` events (names starting ``bench.``),
which the profiler puts on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    device_ops: list          # [(name, seconds)], most time first
    programs: list            # [(XLA module, seconds)], all, most time first
    idle_gaps: list           # [(host span open in the gap, seconds)], longest first
    idle_by_span: dict        # host span -> idle seconds
    program_runs: dict = dataclasses.field(default_factory=dict)
    # XLA module -> [(start_ns, end_ns)] of its runs wholly in the window
    clock_offset_ns: float = 0.0  # trace clock minus time.monotonic_ns


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]`` between merged
    busy intervals."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _innermost(spans, t: float) -> str:
    """The shortest host span open at time ``t`` (``host`` if none)."""
    best, best_len = "host", None
    for name, s, e in spans:
        if s <= t < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce_events(devices, host_spans, window, top: int = 10):
    """``devices``: one ``(ops, programs)`` pair per device, each a list of
    ``(name, start_ns, end_ns)``; ``host_spans``: ``(name, start_ns,
    end_ns)``; ``window``: ``(lo, hi)`` in ns.  Returns a
    :class:`TraceSummary`."""
    lo, hi = window
    per_op: dict = {}
    per_program: dict = {}
    runs: dict = {}
    busy_total = 0.0
    all_gaps = []
    for ops, programs in devices:
        clipped = [(max(s, lo), min(e, hi)) for _, s, e in ops + programs
                   if e > lo and s < hi]
        for name, s, e in ops:
            if e > lo and s < hi:
                per_op[name] = per_op.get(name, 0.0) + (min(e, hi) - max(s, lo))
        for name, s, e in programs:
            if e > lo and s < hi:
                name = program_name(name)
                per_program[name] = (per_program.get(name, 0.0)
                                     + (min(e, hi) - max(s, lo)))
                if lo <= s and e <= hi:
                    runs.setdefault(name, []).append((s, e))
        busy = merged(clipped)
        busy_total += sum(e - s for s, e in busy)
        all_gaps += gaps(busy, lo, hi)
    n_dev = max(1, len(devices))
    spans = [sp for sp in host_spans if sp[0] != WINDOW_SPAN]
    idle_by_span: dict = {}
    tagged = []
    for s, e in all_gaps:
        name = _innermost(spans, (s + e) / 2)
        idle_by_span[name] = idle_by_span.get(name, 0.0) + (e - s) / 1e9 / n_dev
        tagged.append((name, (e - s) / 1e9))

    def per_second(times):
        return sorted(((k, v / 1e9 / n_dev) for k, v in times.items()),
                      key=lambda kv: -kv[1])

    ops = per_second(per_op)
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / 1e9 / n_dev,
        device_ops=ops[:top],
        programs=per_second(per_program),
        idle_gaps=sorted(tagged, key=lambda kv: -kv[1])[:top],
        idle_by_span=idle_by_span,
        program_runs=runs,
    )


def op_name(text: str) -> str:
    """``%fusion.12`` of a TPU op's ``%fusion.12 = f32[...] fusion(...)``
    (a loop's ``%while`` event spans the ops of its body, which have
    events of their own)."""
    return text.split(" = ", 1)[0]


def program_name(text: str) -> str:
    """``jit_while`` of a TPU program's ``jit_while(5963902706534273192)``:
    the programs of one name, whatever their fingerprint, add up."""
    return text.split("(", 1)[0]


def _events(line) -> list:
    return [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events if ev.duration_ns > 0]


def read_xplane(path: str):
    from jax.profiler import ProfileData

    return read_profile(ProfileData.from_file(path))


def read_profile(data):
    """``(devices, host_spans, window)`` from a ``jax.profiler.ProfileData``
    (see :func:`reduce_events`)."""
    devices, host_spans, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                ops = _events(lines[OPS_LINE])
                programs = (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else [])
            else:
                ops = [ev for line in lines.values() for ev in _events(line)]
                programs = []
            if ops or programs:
                devices.append((ops, programs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        span = (ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                        if ev.name == WINDOW_SPAN:
                            window = span[1:]
                        else:
                            host_spans.append(span)
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN} span in {path}")
    return devices, host_spans, window


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(paths)}")
    return paths[0]

