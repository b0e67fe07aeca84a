"""Profiler capture and the reduction from a device trace to numbers.

``capture`` runs a callable under ``jax.profiler`` inside a host span
named :data:`SPAN`, and ``load_events`` reads the ``.xplane.pb`` it
wrote into plain :class:`Event` tuples.  ``reduce`` turns those into the
traced window's length, the device's busy time (the union of the
intervals in which an operation ran on it), the time of Pallas kernels,
and the breakdowns the result line carries.  The reduction works on the
tuples alone, so a trimmed recorded trace checks it
(``tests/test_trace.py``).

How a TPU trace is laid out (read by hand from TPU v5 lite traces; a
trimmed one is ``tests/data/trace_fit.json.gz``): each chip is a plane
``/device:TPU:<i>``.  Its line ``XLA Modules`` holds one event per
program run (``jit__swap_iter(<fingerprint>)``), and its line ``XLA
Ops`` one event per HLO operation, named by the operation's whole HLO
text (``%fusion.12 = f32[...] fusion(...), ...``).  A ``while`` or
``conditional`` there spans the operations of its body, which appear
as events of their own.  A Pallas kernel is a custom call whose text
holds ``custom_call_target="tpu_custom_call"``, named after the kernel's
function (``%stream_top2_kernel.1 = ... custom-call(...)``).  Host
threads are lines of the plane ``/host:CPU``; the thread ``python``
holds the Python tracer's function events (``$engine.py:336
host_read``).
"""

from __future__ import annotations

import glob
import os
import shutil
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional

SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int


def capture(fn: Callable[[], object], out_dir: str) -> str:
    """Run ``fn`` traced; return the path of the trace written."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    try:
        with jax.profiler.TraceAnnotation(SPAN):
            fn()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {out_dir}, "
                           f"found {found}")
    return found[0]


def load_events(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [Event(plane.name, line.name, ev.name, int(ev.start_ns),
                  int(ev.duration_ns))
            for plane in pd.planes for line in plane.lines
            for ev in line.events]


def _union(intervals: Iterable[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(iv) for iv in out]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _name_gaps(gaps: List[tuple], host: List[Event],
               named: int = 500) -> Dict[str, float]:
    """Idle time by the host event that overlaps each gap most (of
    those, the shortest: the innermost call).  The ``named`` longest gaps
    are named; the rest are summed apart."""
    import numpy as np

    out: Dict[str, float] = {}
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    starts = np.array([e.start_ns for e in host], np.int64)
    ends = starts + np.array([e.dur_ns for e in host], np.int64)
    for i, (a, b) in enumerate(gaps):
        name = "shorter gaps"
        if i < named:
            name = "no host event"
            if host:
                cover = np.minimum(ends, b) - np.maximum(starts, a)
                most = cover.max()
                if most > 0:
                    dur = np.where(cover == most, ends - starts, np.inf)
                    name = host[int(np.argmin(dur))].name
        out[name] = out.get(name, 0) + (b - a)
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def _leaves(ops: List[tuple]) -> List[tuple]:
    """The operations with no other operation inside them: a ``while``
    or ``conditional`` spans its body's events on the same line."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, o in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1][0] < o[1]:
            continue
        out.append(o)
    return out


def reduce(events: List[Event], top: int = 10
           ) -> Optional[Dict[str, object]]:
    """Numbers of the traced window, or ``None`` where the trace holds
    no window span or no device operation inside it.

    Returns ``window_s`` (the span's length), ``busy_s`` (union of device
    operation intervals, averaged over the chips that ran any),
    ``idle_share``, ``pallas_s`` (summed device time of Pallas kernels,
    per chip; ``None`` where none ran), ``device_ops`` (the ``top``
    operation names by device time, innermost operations only) and
    ``idle_gaps`` (device idle time inside the window, grouped by
    the host event that covered most of each gap)."""
    spans = [e for e in events if e.name == SPAN]
    if not spans:
        return None
    lo = min(e.start_ns for e in spans)
    hi = max(e.start_ns + e.dur_ns for e in spans)
    per_chip: Dict[str, List[tuple]] = {}
    for e in events:
        if not (e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE):
            continue
        a, b = _clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
        if b > a:
            per_chip.setdefault(e.plane, []).append((a, b, e.name))
    if not per_chip:
        return None
    op_time: Dict[str, float] = {}
    pallas_ns = 0
    for ops in per_chip.values():
        for a, b, name in _leaves(ops):
            short = op_name(name)
            op_time[short] = op_time.get(short, 0) + (b - a)
            if PALLAS_MARK in name:
                pallas_ns += b - a
    chips = len(per_chip)
    unions = {p: _union((a, b) for a, b, _ in ops)
              for p, ops in per_chip.items()}
    busy_ns = sum(b - a for u in unions.values() for a, b in u) / chips
    window_ns = hi - lo
    # Idle gaps of the first chip.
    first = unions[sorted(unions)[0]]
    gaps, t = [], lo
    for a, b in first:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    gap_time = _name_gaps(gaps, [e for e in events
                                 if not e.plane.startswith(DEVICE_PREFIX)
                                 and e.name != SPAN and e.dur_ns > 0])

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "pallas_s": pallas_ns / chips / 1e9 if pallas_ns else None,
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(gap_time),
    }
