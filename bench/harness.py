"""The benchmark harness: one run of one cell, driven by data.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``configs/<config>.json``, its traffic and the limits
of its correctness check are ``workloads/<cell>.json``, and each of its
per-layer metrics is read by ``metrics/<metric>.py`` (a function
``read(ctx)`` that returns a number, or ``None`` where it finds nothing
to read).  The configuration names its data set, made by
``datasets/<dataset>.py`` (``generate(n, seed, d)``, see ``datagen``),
and the traffic names its job, the class ``Job`` of ``<job>.py``
(``fit``: ``fit.py``).  Each is found by its name alone (``load``), so
adding a cell, a metric, a data set or a job adds files and entries
only.

A run: check the device, enable the persistent compile cache in the
checkout, set up (data, program, warm-up: ``setup_s``), measure for
``--seconds``, with ``--trace 1`` trace one segment more, read the peak
device memory, free the program, run the reference check, and print the
result as the last line of standard output.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

# Sizes a rehearsal (``--rehearse``, or a test) runs at: each key of a
# configuration or traffic that is present is cut to at most this.
REHEARSAL = {"n": 640}


class Cell:
    """One cell's entries, read from ``BENCHMARK.json`` and its files."""

    def __init__(self, name: str, root: str = ROOT,
                 shrink: Optional[dict] = None):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
        entry = entries[name]
        self.name = name
        self.chips = int(entry["chips"])
        with open(os.path.join(HERE, "configs", entry["config"] + ".json")) as f:
            self.config = json.load(f)
        with open(os.path.join(HERE, "workloads", name + ".json")) as f:
            spec = json.load(f)
        self.traffic = spec["traffic"]
        self.limits: Dict[str, float] = spec["limits"]
        if shrink:
            for part in (self.config, self.traffic):
                for key, most in shrink.items():
                    if key in part:
                        part[key] = min(int(part[key]), most)
        self.end_to_end = [m["name"] for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m["name"] for m in bench["per_layer"]
                          if name in m.get("workloads", [])
                          or ("workloads" not in m
                              and m["moves"] in self.end_to_end)]
        self.units = {m["name"]: m["unit"]
                      for m in bench["end_to_end"] + bench["per_layer"]}

    @staticmethod
    def names(root: str = ROOT) -> list:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            return [w["name"] for w in json.load(f)["workloads"]]


def load(folder: str, name: str, attr: str):
    """``attr`` of the file ``<folder>/<name>.py`` of the benchmark
    (``folder`` ``""`` for the benchmark's own directory).  A name with
    no such file, or a file without ``attr``, raises ``LookupError``
    naming those that have it."""
    where = os.path.join(HERE, folder)
    path = os.path.join(where, name + ".py")
    if os.path.isfile(path):
        spec = importlib.util.spec_from_file_location(
            f"bench_{folder or 'job'}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if hasattr(mod, attr):
            return getattr(mod, attr)
    defines = re.compile(rf"^(def {attr}\(|class {attr}\b|{attr} = )", re.M)
    have = []
    for file in sorted(os.listdir(where)):
        if file.endswith(".py"):
            with open(os.path.join(where, file)) as f:
                if defines.search(f.read()):
                    have.append(file[:-3])
    raise LookupError(f"{name!r}: no file {os.path.join(folder, name)}.py "
                      f"with {attr!r}; the names that have one: {have}")


def make_job(cell: Cell, seed: int):
    job = load("", cell.traffic["job"], "Job")
    return job(cell.config, cell.traffic, seed)


def read_metric(name: str, ctx: dict) -> Optional[float]:
    value = load("metrics", name, "read")(ctx)
    return None if value is None else float(value)


def devices_ok(chips: int) -> Optional[str]:
    """``None`` where JAX sees a TPU with at least ``chips`` chips, else
    why not."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX's devices are {devs[0].platform!r}"
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devs)}"
    return None


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """Everything of a run after the device check; returns the result.
    ``t_start`` is the host clock at process start."""
    from bench import trace as tracing
    from repro.runtime import compile_cache

    import jax

    compile_cache.enable(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    job = make_job(cell, seed)
    job.setup()
    setup_s = time.perf_counter() - t_start
    e2e = job.window(seconds)
    e2e["setup_s"] = setup_s
    summary = None
    if trace:
        path = tracing.capture(job.traced, TRACE_DIR)
        summary = tracing.reduce(tracing.load_events(path))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    device = device_info(cell.chips)
    job.release()
    gc.collect()
    numbers = job.check()
    correct = all(math.isfinite(v) and v <= float(cell.limits[name])
                  for name, v in numbers.items())
    # A number that is not finite prints as a string: the line stays JSON.
    checks = {name: {"value": v if math.isfinite(v) else repr(v),
                     "limit": float(cell.limits[name])}
              for name, v in numbers.items()}
    if trace:
        ctx = dict(job.layer_context(), trace=summary)
        metrics = {}
        for name in cell.per_layer:
            v = read_metric(name, ctx)
            if v is not None:
                metrics[name] = v
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    else:
        metrics = {name: e2e[name] for name in cell.end_to_end}
    result = {"correct": correct, "attempted": job.attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="One run of one cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the cell at a tiny size on any device; "
                         "prints the checks, no result, and exits 1")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program under {ROOT}/src", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = Cell(args.workload, shrink=REHEARSAL if args.rehearse else None)
    if not args.rehearse:
        why = devices_ok(cell.chips)
        if why:
            print(why, file=sys.stderr)
            return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    result["metrics"] = {k: {"value": v, "unit": cell.units[k]}
                         for k, v in result["metrics"].items()}
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    if args.rehearse:
        print(f"rehearsal, not a result: {json.dumps(result)}",
              file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0
