"""Device time by program stage, and idle gaps by host span.

The program names its device stages with ``jax.named_scope`` (the names
in :data:`SCOPES`) and its host stages with ``repro.*`` spans
(``repro.core.engine.span``).  A stage's scope path is each HLO
operation's ``op_name`` metadata, e.g.
``jit(_swap_iter)/swap/while/body/search/…/fresh/prep/jit(_pad)/pad``.
A TPU trace does not carry it: an op event's stats hold its device
offset and duration only (read from TPU v5 lite traces).  So
``load_events`` reads it from the text XLA dumps of each optimized
module (``--xla_dump_to=<dir> --xla_dump_hlo_as_text``), by the op's
module (the ``XLA Modules`` event around it) and its instruction (the
head of the op event's HLO text), and keeps it as a sixth field beside
the five of ``trace.Event``; ``trace.reduce`` reads these events as it
reads its own, and ``reduce`` here adds:

* ``scopes`` — device seconds of innermost operations, by each program
  scope that appears in their path (scopes nest: ``prep`` time is also
  ``fresh`` or ``stats`` time), averaged over the chips that ran any;
* ``unscoped`` — device seconds of innermost operations under no program
  scope (an operation the compiler added takes the scopes of the control
  flow around it, see ``_leaf_scopes``);
* ``idle_spans`` — device idle time inside the window, grouped by the
  innermost ``repro.*`` host span over each gap.

From the command line it measures one cell's fit by stage on the chip::

    python bench/scopes.py --workload mnist-l2.fit --seed 7 --fits 3

It sets the cell up as ``run.py`` does, but compiles every program
afresh (no persistent cache) so that XLA dumps each module, runs the
round's first fit warm ``--fits`` times untraced and ``--fits`` times
traced (each in a trace of its own), and prints one JSON line: the walls
of both, the last traced fit's ``trace.reduce`` numbers and the three
above, and the fit's fresh and re-computed distance evaluations.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

SCOPES = ("build", "swap", "search", "exact", "fresh", "pic", "stats",
          "top2", "carry", "prep")
HOST_PREFIX = "repro."
MODULES_LINE = "XLA Modules"
HLO_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_hlo")
# ``%pad.25 = f32[70016,896]{1,0:T(8,128)} pad(`` -> everything before
# the operands: the same in a dumped module and in an op event's text.
_HEAD = re.compile(r"\s*(?:ROOT )?(%\S+ = .*? [a-z][\w.\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int
    scope: str = ""


def op_names(hlo_dir: str) -> Dict[Tuple[str, str], str]:
    """``(module, instruction head) -> op_name`` of every optimized
    module XLA dumped as text under ``hlo_dir``."""
    out: Dict[Tuple[str, str], str] = {}
    for path in sorted(glob.glob(os.path.join(hlo_dir, "*.txt"))):
        if not path.endswith("after_optimizations.txt"):
            continue
        module = None
        with open(path) as f:
            for text in f:
                if module is None:
                    if text.startswith("HloModule "):
                        module = text.split()[1].rstrip(",")
                    continue
                head, name = _HEAD.match(text), _OP_NAME.search(text)
                if head and name:
                    out.setdefault((module, head.group(1)), name.group(1))
    return out


def load_events(path: str, hlo_dir: Optional[str] = None) -> List[Event]:
    """The trace's events, each device op with its scope path where
    ``hlo_dir`` holds the dumped module it ran in ("" elsewhere)."""
    from jax.profiler import ProfileData

    names = op_names(hlo_dir) if hlo_dir else {}
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((int(ev.start_ns), ev.name.split("(")[0])
                      for ev in lines.get(MODULES_LINE, ()))
        starts = [m[0] for m in mods]
        ops = plane.name.startswith(trace.DEVICE_PREFIX)
        for line, events in lines.items():
            for ev in events:
                scope = ""
                if ops and line == trace.OPS_LINE and mods:
                    i = bisect.bisect_right(starts, int(ev.start_ns)) - 1
                    head = _HEAD.match(ev.name)
                    if i >= 0 and head:
                        scope = names.get((mods[i][1], head.group(1)), "")
                out.append(Event(plane.name, line, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns),
                                 scope))
    return out


def scope_names(path: str) -> set:
    """The program scopes among the components of an op's scope path."""
    return set(path.split("/")) & set(SCOPES)


def _leaf_scopes(ops: List[tuple]) -> List[tuple]:
    """``(start, end, scopes)`` of the innermost operations of one chip.

    An operation the compiler added (a copy of a branch operand, say)
    has no scope path of its own.  It takes the scopes of the nearest
    ``while`` or ``conditional`` event around it that has some: the
    control flow it runs in."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    scoped: List[set] = []
    leaves, stack = [], []
    for i, (a, b, path) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        scoped.append(scope_names(path) or (scoped[stack[-1]] if stack
                                            else set()))
        if i + 1 < len(ops) and ops[i + 1][0] < b:
            stack.append(i)
        else:
            leaves.append((a, b, scoped[i]))
    return leaves


def reduce(events: List[Event], top: int = 10
           ) -> Optional[Dict[str, object]]:
    """``scopes``, ``unscoped`` and ``idle_spans`` of the traced window,
    or ``None`` where the trace holds no window span or no device
    operation inside it (as ``trace.reduce``)."""
    spans = [e for e in events if e.name == trace.SPAN]
    if not spans:
        return None
    lo = min(e.start_ns for e in spans)
    hi = max(e.start_ns + e.dur_ns for e in spans)
    per_chip: Dict[str, List[tuple]] = {}
    for e in events:
        if not (e.plane.startswith(trace.DEVICE_PREFIX)
                and e.line == trace.OPS_LINE):
            continue
        a, b = trace._clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
        if b > a:
            per_chip.setdefault(e.plane, []).append((a, b, e.scope))
    if not per_chip:
        return None
    chips = len(per_chip)
    scoped: Dict[str, float] = {}
    unscoped = 0
    for ops in per_chip.values():
        for a, b, names in _leaf_scopes(ops):
            for name in names:
                scoped[name] = scoped.get(name, 0) + (b - a)
            if not names:
                unscoped += b - a
    first = trace._union((a, b) for a, b, _ in
                         per_chip[sorted(per_chip)[0]])
    gaps, t = [], lo
    for a, b in first:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    host = [e for e in events if e.name.startswith(HOST_PREFIX)
            and not e.plane.startswith(trace.DEVICE_PREFIX) and e.dur_ns > 0]
    idle = trace._name_gaps(gaps, host)
    return {
        "scopes": {k: v / chips / 1e9 for k, v in sorted(scoped.items())},
        "unscoped": unscoped / chips / 1e9,
        "idle_spans": [[k, v / 1e9] for k, v in
                       sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def main(argv) -> int:
    import argparse
    import json
    import shutil
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fits", type=int, default=3)
    args = ap.parse_args(argv)
    from bench import harness

    shutil.rmtree(HLO_DIR, ignore_errors=True)
    os.environ["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={HLO_DIR}",
         "--xla_dump_hlo_as_text"]).strip()
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    cell = harness.Cell(args.workload)
    why = harness.devices_ok(cell.chips)
    if why:
        print(why, file=sys.stderr)
        return 2
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    job = harness.make_job(cell, args.seed)
    job.setup()
    walls: Dict[str, List[float]] = {"untraced_s": [], "traced_s": []}

    def timed(key):
        t0 = time.perf_counter()
        job.traced()
        walls[key].append(time.perf_counter() - t0)

    for _ in range(args.fits):
        timed("untraced_s")
    out_dir = harness.TRACE_DIR
    for _ in range(args.fits):
        path = trace.capture(lambda: timed("traced_s"), out_dir)
    events = load_events(path, HLO_DIR)
    shutil.rmtree(out_dir, ignore_errors=True)
    shutil.rmtree(HLO_DIR, ignore_errors=True)
    report = job.round[0].report_
    result = dict(walls, device=harness.device_info(cell.chips),
                  fresh_evals=report.distance_evals,
                  refresh_evals=getattr(report, "refresh_evals", None),
                  trace=trace.reduce(events), **(reduce(events) or {}))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
