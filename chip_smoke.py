"""Smoke run of the k-medoids main path on a TPU, through the entry points
a user calls.  One process; it proves that the system runs on the chip
and gives the right answers.  It is not a benchmark.

    python chip_smoke.py              # one chip, the four phases below
    python chip_smoke.py --chips 4    # sharded fit over four chips vs one
    JAX_PLATFORMS=cpu python chip_smoke.py --n 600   # rehearsal; exits 1

Phases on one chip:

1. Paper-scale fit: ``KMedoids(k=5, solver="banditpam_pp")`` on
   ``mnist_like(70000)`` (d=784, l2), fitted twice (cold, then warm) to
   separate compile from run.  The reported loss must match a dense
   reference loss of the returned medoids.
2. Exact tier: ``banditpam`` on the chip returns the medoids of
   ``fastpam1`` run at ``highest`` matmul precision (n=2000, d=784).
3. The l1 regime: ``KMedoids(k=5, metric="l1")`` on ``scrna_like(20000)``
   (d=1000), with the phase-1 loss check.
4. Serving: a ``MedoidService`` fitted like phase 1 (same medoids)
   answers ``predict`` requests of 256 and 4096 rows; labels must match a
   dense argmin.

The dense references form the differences explicitly (no matmul, so no
matmul precision enters them) and sum the loss in float64 on the host.

Each phase also checks that the resolved stats backend is ``"pallas"``
and that its top-2 pass lowers to a Mosaic kernel (``tpu_custom_call``),
not to interpret mode.  Every failed check prints ``FAIL`` and makes the
exit code 1; a phase that raises counts as failed and the next phase
still runs.  Off a TPU the script exits 2 at once, or, given ``--n``,
runs the phases as a rehearsal and exits 1.  Only a run in which every
check passed on a TPU ends with the line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import KMedoids  # noqa: E402
from repro.core import datasets, engine, tuning  # noqa: E402
from repro.runtime import compile_cache  # noqa: E402
from repro.serve import MedoidService  # noqa: E402

K = 5
N_FIT = 70_000        # phase 1 (the paper's MNIST scale)
N_EXACT = 2_000       # phase 2
N_L1 = 20_000         # phase 3
REQUEST_ROWS = (256, 256, 256, 4096, 4096)
LOSS_RTOL = 1e-5      # fit loss vs the dense reference loss
LABEL_RTOL = 1e-5     # a served label may differ only at such a near-tie
# Four chips vs one: stratified per-shard sampling draws other reference
# batches than the one-chip fit, so equal medoids are likely but not
# guaranteed; the sharded loss must be within this of the one-chip loss.
DIST_LOSS_RTOL = 1e-3


class Checks:
    """Records check outcomes; any failure makes the exit code 1."""

    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail="") -> bool:
        print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name: str, fn, *args):
        print(f"== {name}", flush=True)
        try:
            return fn(self, *args)
        except Exception:           # reported, and the run exits 1
            traceback.print_exc()
            self.failed.append(name)
            return None


def _dense_block(metric: str):
    def block(xc, med):
        diff = xc[:, None, :] - med[None, :, :]
        if metric == "l1":
            return jnp.sum(jnp.abs(diff), axis=-1)
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    return block


def dense_distances(x: np.ndarray, med: np.ndarray, metric: str,
                    rows: int = 2048) -> np.ndarray:
    """``[n, d] x [k, d] -> [n, k]`` from explicit differences, in row
    blocks of ``rows`` (the repo's distance code is not used)."""
    n = x.shape[0]
    xp = np.zeros((-(-n // rows) * rows, x.shape[1]), np.float32)
    xp[:n] = x
    block = _dense_block(metric)
    out = jax.jit(lambda a, m: jax.lax.map(lambda xc: block(xc, m), a))(
        jnp.asarray(xp.reshape(-1, rows, x.shape[1])), jnp.asarray(med))
    return np.asarray(out).reshape(-1, med.shape[0])[:n]


def dense_loss(x: np.ndarray, medoids, metric: str) -> float:
    d = dense_distances(x, x[np.asarray(medoids)], metric)
    return float(np.sum(d.min(axis=1).astype(np.float64)))


def check_path(chk: Checks, metric: str, x: np.ndarray) -> None:
    """The stats backend a default fit resolves to, and whether its
    streaming top-2 pass at this shape lowers to a Mosaic kernel."""
    name = engine.resolve_stats_backend("auto", metric)
    chk.check(f"{metric}: stats backend", name == "pallas", name)
    be = engine.get_stats_backend(name)
    xs = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    ms = jax.ShapeDtypeStruct((K, x.shape[1]), jnp.float32)
    text = jax.jit(lambda a, m: be.top2(a, m, metric=metric)).lower(
        xs, ms).as_text()
    chk.check(f"{metric}: top-2 pass lowers to a Mosaic kernel",
              "tpu_custom_call" in text, f"n={x.shape[0]}, d={x.shape[1]}")
    cfg = tuning.resolve_tile_config(x.shape[0], x.shape[1], K,
                                     backend=name)
    print(f"   tile config: {cfg}", flush=True)


def check_loss(chk: Checks, what: str, x, medoids, loss, metric) -> None:
    ref = dense_loss(x, medoids, metric)
    rel = abs(loss - ref) / ref
    chk.check(f"{what}: loss vs dense reference", rel <= LOSS_RTOL,
              f"fit {loss!r}, dense {ref!r}, rel {rel:.3e} "
              f"(limit {LOSS_RTOL:g})")


def timed_fit(est: KMedoids, x: np.ndarray):
    # KMedoids.fit returns host arrays (medoids, labels, loss), so the
    # device work is finished when it returns.
    t0 = time.perf_counter()
    est.fit(x)
    return time.perf_counter() - t0


def describe(report) -> str:
    return (f"medoids {sorted(report.medoids.tolist())}, loss "
            f"{float(report.loss)!r}, n_swaps {report.n_swaps}, converged "
            f"{report.converged}, ledger {report.ledger()}, wall_by_phase "
            f"{ {p: round(v, 3) for p, v in report.wall_by_phase.items()} }")


def phase_fit(chk: Checks, n: int):
    x = datasets.mnist_like(n)
    print(f"   data: mnist_like n={n} d={x.shape[1]}", flush=True)
    check_path(chk, "l2", x)
    est = KMedoids(k=K, solver="banditpam_pp", metric="l2", seed=0)
    cold = timed_fit(est, x)
    first = est.report_
    warm = timed_fit(est, x)
    print(f"   fit wall: cold (compile + run) {cold:.3f} s, warm {warm:.3f} "
          f"s, compile ~{cold - warm:.3f} s", flush=True)
    print(f"   {describe(est.report_)}", flush=True)
    chk.check("phase 1: warm fit repeats the cold fit",
              np.array_equal(first.medoids, est.report_.medoids)
              and first.loss == est.report_.loss)
    check_loss(chk, "phase 1", x, est.medoids_, est.loss_, "l2")
    return x, np.sort(est.medoids_)


def phase_exact(chk: Checks, n: int) -> None:
    x = datasets.mnist_like(n)
    print(f"   data: mnist_like n={n} d={x.shape[1]}", flush=True)
    check_path(chk, "l2", x)
    bp = KMedoids(k=K, solver="banditpam", metric="l2", seed=0)
    wall = timed_fit(bp, x)
    print(f"   banditpam ({wall:.3f} s incl. compile): "
          f"{describe(bp.report_)}", flush=True)
    with jax.default_matmul_precision("highest"):
        ref = KMedoids(k=K, solver="fastpam1", metric="l2").fit(x)
    print(f"   fastpam1 (highest precision): {describe(ref.report_)}",
          flush=True)
    chk.check("phase 2: banditpam medoids == fastpam1 medoids",
              sorted(bp.medoids_) == sorted(ref.medoids_),
              f"{sorted(bp.medoids_.tolist())} vs "
              f"{sorted(ref.medoids_.tolist())}")


def phase_l1(chk: Checks, n: int) -> None:
    x = datasets.scrna_like(n)
    print(f"   data: scrna_like n={n} d={x.shape[1]} "
          f"({np.mean(x == 0):.1%} zeros)", flush=True)
    check_path(chk, "l1", x)
    est = KMedoids(k=K, metric="l1", seed=0)
    wall = timed_fit(est, x)
    print(f"   fit wall (incl. compile) {wall:.3f} s", flush=True)
    print(f"   {describe(est.report_)}", flush=True)
    check_loss(chk, "phase 3", x, est.medoids_, est.loss_, "l1")


def phase_serve(chk: Checks, fitted) -> None:
    if fitted is None:
        raise RuntimeError("phase 1 produced no medoids to serve")
    x, medoids = fitted
    svc = MedoidService(K, "l2", solver="banditpam_pp", solver_params={},
                        seed=0).fit(x)
    chk.check("phase 4: service medoids == phase-1 medoids",
              np.array_equal(np.sort(svc.last_report.medoids), medoids))
    queries = datasets.mnist_like(sum(REQUEST_ROWS), seed=1)
    med_pts = np.asarray(svc.medoid_points)
    lo = 0
    for rows in REQUEST_ROWS:
        q = queries[lo:lo + rows]
        lo += rows
        t0 = time.perf_counter()
        got = svc.predict(q)
        wall = time.perf_counter() - t0
        ref = dense_distances(q, med_pts, "l2")
        want = ref.argmin(axis=1)
        # A label may differ from the dense argmin only where the two
        # medoids are equally near to within LABEL_RTOL.
        gap = ref[np.arange(rows), got] - ref[np.arange(rows), want]
        bad = int(np.sum(gap > LABEL_RTOL * ref[np.arange(rows), want]))
        chk.check(f"phase 4: predict {rows} rows vs dense argmin", bad == 0,
                  f"{int(np.sum(got != want))} differ, {bad} beyond a "
                  f"near-tie; {wall * 1e3:.2f} ms")


def phase_four_chips(chk: Checks, n: int) -> None:
    from repro.core.distributed import DistributedBanditPAM, default_mesh

    chk.check("four devices", len(jax.devices()) == 4,
              f"{len(jax.devices())}")
    x = datasets.mnist_like(n)
    print(f"   data: mnist_like n={n} d={x.shape[1]}", flush=True)
    check_path(chk, "l2", x)
    mesh = default_mesh()
    # Where the sharded fit puts its operands: the same placement
    # helpers DistributedBanditPAM.fit calls.
    placement = DistributedBanditPAM(K, mesh, reuse="pic")
    data_sh = placement._shard_data(jnp.asarray(x))
    ring = placement._pic_layout(n, jax.random.PRNGKey(0))[4].cols
    for what, arr, axis in (("data", data_sh, 0), ("PIC ring", ring, 1)):
        shards = [(s.device.id, s.data.shape) for s in arr.addressable_shards]
        chk.check(f"{what} sharded over 4 devices",
                  len(arr.sharding.device_set) == 4
                  and all(4 * shp[axis] == arr.shape[axis]
                          for _, shp in shards),
                  f"global {arr.shape}, shards {shards}")
    del data_sh, ring

    dist = KMedoids(k=K, solver="banditpam_dist", metric="l2", seed=0,
                    mesh=mesh, reuse="pic")
    wall_d = timed_fit(dist, x)
    print(f"   banditpam_dist ({wall_d:.3f} s incl. compile): "
          f"{describe(dist.report_)}", flush=True)
    one = KMedoids(k=K, solver="banditpam", metric="l2", seed=0,
                   reuse="pic")
    wall_1 = timed_fit(one, x)
    print(f"   banditpam, one chip ({wall_1:.3f} s incl. compile): "
          f"{describe(one.report_)}", flush=True)
    check_loss(chk, "four chips", x, dist.medoids_, dist.loss_, "l2")
    check_loss(chk, "one chip", x, one.medoids_, one.loss_, "l2")
    rel = abs(dist.loss_ - one.loss_) / one.loss_
    chk.check("four-chip loss vs one-chip loss", rel <= DIST_LOSS_RTOL,
              f"rel {rel:.3e} (limit {DIST_LOSS_RTOL:g}); same medoids: "
              f"{sorted(dist.medoids_) == sorted(one.medoids_)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fit and its one-chip "
                         "comparison")
    ap.add_argument("--n", type=int, default=None,
                    help=f"phase-1 size (default {N_FIT}); phases 2 and 3 "
                         f"take at most this many points.  Needed off a "
                         f"TPU, where the run is a rehearsal")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    platform = dev.platform
    print(f"device: {platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}", flush=True)
    if platform != "tpu" and args.n is None:
        print(f"FAIL no TPU (platform {platform!r}); pass --n to rehearse "
              f"at a small size", flush=True)
        return 2
    print(f"compile cache: {compile_cache.enable(ROOT)}", flush=True)
    n = N_FIT if args.n is None else args.n
    chk = Checks()
    chk.check("platform is tpu", platform == "tpu", platform)
    if args.chips == 4:
        chk.phase("four chips: banditpam_dist vs one chip",
                  phase_four_chips, n)
    else:
        fitted = chk.phase("phase 1: paper-scale fit", phase_fit, n)
        chk.phase("phase 2: exact tier", phase_exact, min(N_EXACT, n))
        chk.phase("phase 3: l1 regime", phase_l1, min(N_L1, n))
        chk.phase("phase 4: serving", phase_serve, fitted)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device 0 peak bytes in use: {stats['peak_bytes_in_use']}",
              flush=True)
    if chk.failed:
        print(f"FAILED: {chk.failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
