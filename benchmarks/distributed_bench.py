"""Distributed-engine sweep: ``banditpam_dist`` on a simulated
multi-device mesh vs the single-device solver at fixed (n, k), including
the ``reuse="pic"`` sharded-cache row.

Per row it records the loss, wall clock, the fresh/cached ledger, the
cached fraction, and the driver's per-phase jit dispatch counts — and
ASSERTS that the fused sharded BUILD issued ONE dispatch for the whole
phase (not one per selection): the regression guard for the
fori_loop-fused BUILD, enforced wherever the bench runs (CI uploads the
JSON as an artifact).

On an accelerator the rows run in this process over its real devices:
a chip belongs to one process, so no child may ask for it.  On the CPU
the devices are simulated, and the device-count flag must be set before
JAX starts, so the rows run in a child pinned to the CPU; its results
come back as JSON.  Either way they are emitted as the usual CSV rows
(and serialised to ``BENCH_distributed.json`` by ``benchmarks/run.py
--json``).

Knobs: ``REPRO_BENCH_DEVICES`` (simulated CPU devices, default 8),
``REPRO_BENCH_PALLAS=1`` adds the interpret-mode Pallas backend row
off-accelerator (same convention as ``core_bench``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax

from .common import FULL, emit


def rows(n: int, k: int, backends) -> dict:
    """The sweep rows, measured in this process over every local device."""
    from repro.api import KMedoids
    from repro.core import datasets
    from repro.core.distributed import default_mesh

    data = datasets.make("mnist_like", n, seed=0)
    mesh = default_mesh()
    out = {}
    cases = [("banditpam", {"baseline": "leader"}),
             ("banditpam_dist", {"mesh": mesh}),
             ("banditpam_dist[pic]", {"mesh": mesh, "reuse": "pic"})]
    for name, params in cases:
        solver = name.split("[")[0]
        for backend in backends:
            t0 = time.perf_counter()
            est = KMedoids(k, solver=solver, metric="l2", seed=0,
                           backend=backend, **params).fit(data)
            wall = time.perf_counter() - t0
            r = est.report_
            led = r.ledger()
            total = led["fresh"] + led["cached"]
            out[f"{name}[{backend}]"] = {
                "loss": float(r.loss),
                "wall_s": round(wall, 3),
                "wall_by_phase": {p: round(v, 4)
                                  for p, v in r.wall_by_phase.items()},
                "ledger": led,
                "cached_fraction": round(led["cached"] / total, 4),
                "dispatches_by_phase": dict(r.dispatches_by_phase),
                "n_swaps": int(r.n_swaps),
                "converged": bool(r.converged),
            }
    return out


_CHILD = textwrap.dedent("""
    import json, sys
    from benchmarks.distributed_bench import rows
    print(json.dumps(rows(int(sys.argv[1]), int(sys.argv[2]),
                          sys.argv[3].split(","))))
""")


def _assert_single_dispatch_build(rows: dict) -> None:
    """CI guard: the fused sharded BUILD is one jit dispatch per phase."""
    for name, row in rows.items():
        if not name.startswith("banditpam_dist"):
            continue
        d = row["dispatches_by_phase"]
        if d.get("build") != 1:
            raise AssertionError(
                f"{name}: sharded BUILD issued {d.get('build')} dispatches "
                f"— the fori_loop fusion regressed (expected 1 per phase)")
        # One fused step per iteration: every accepted swap plus — only
        # when the fit converged — the final non-improving check.  A fit
        # that exhausts max_swaps ends on an accepted swap (no +1).
        want_swap = row["n_swaps"] + (1 if row["converged"] else 0)
        if d.get("swap") != want_swap:
            raise AssertionError(
                f"{name}: sharded SWAP issued {d.get('swap')} dispatches "
                f"for {row['n_swaps']} accepted swaps (expected "
                f"{want_swap} fused steps)")


def sweep(n=None, k=5, devices=None, backends=None):
    if n is None:
        n = 1024 if FULL else 512
    if devices is None:
        devices = int(os.environ.get("REPRO_BENCH_DEVICES", "8"))
    if backends is None:
        backends = ["jnp"]
        if os.environ.get("REPRO_BENCH_PALLAS", "0") == "1":
            backends.append("pallas")
    if jax.default_backend() == "cpu":
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, str(n), str(k),
             ",".join(backends)],
            capture_output=True, text=True, timeout=1800,
            env=dict(os.environ, PYTHONPATH=os.pathsep.join(["src", "."]),
                     JAX_PLATFORMS="cpu",
                     XLA_FLAGS=("--xla_force_host_platform_device_count="
                                f"{devices}")))
        if out.returncode != 0:
            raise RuntimeError(f"distributed bench child failed:\n"
                               f"{out.stderr[-2000:]}")
        got = json.loads(out.stdout.strip().splitlines()[-1])
    else:
        devices = len(jax.devices())
        got = rows(n, k, backends)
    _assert_single_dispatch_build(got)
    for name, row in got.items():
        emit(f"distributed_{name}_n{n}_dev{devices}", row["wall_s"] * 1e6,
             f"loss={row['loss']:.4f};fresh={row['ledger']['fresh']};"
             f"cached_frac={row['cached_fraction']};"
             f"build_dispatches={row['dispatches_by_phase'].get('build')}")
    return {"bench": "distributed", "n": int(n), "k": int(k),
            "devices": int(devices), "rows": got}


def write_json(path="BENCH_distributed.json", **kw) -> str:
    payload = sweep(**kw)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    emit("distributed_json_written", 0.0, path)
    return path


def run():
    sweep()


if __name__ == "__main__":
    run()
