"""Backend-aware tile tuner for the streaming g-stats megakernel.

The streaming kernels (``repro.kernels.stream_g``) and their jnp
equivalents walk the reference set in tiles; two knobs shape the walk:

* ``tm`` — candidate-tile rows (one grid program owns a [tm, ·] strip).
* ``tb`` — reference-tile width.  **Pinned to ``REF_TILE`` (512, the
  engine's historical ``_EXACT_CHUNK``) on every parity-checked path**:
  the per-arm accumulation order is "reduce one tb-wide tile, then add
  tiles in walk order", so changing ``tb`` regroups the f32 adds and
  forfeits bit-parity with the ledger fixtures.  It is a knob for
  throwaway sweeps only.

The feature axis is not a knob: the streaming kernels hold both operand
tiles ([tm, d] and [tb, d]) in VMEM for the whole walk, double-buffered,
and g is not additive across feature chunks, so unlike
``pairwise_distance`` they cannot split d.  Shapes whose tiles exceed the
16 MiB scoped VMEM limit even at the smallest ``tm`` take the tiled-jnp
path.  At k <= 128 that admits padded d up to 1920 at tm=128, 1152 at
tm=256 and 512 at tm=512 (``repro.kernels.vmem``; compiled for a v5e
chip by ``tests/test_tpu_compile.py``).

``resolve_tile_config`` is the single resolution point, keyed on
``(n, d, k, device kind, backend)``.  It consults a measured ledger
first — ``observe()`` records ``FitReport.wall_by_phase`` (or any
benchmark wall) against the config that produced it, and subsequent
resolves for the same shape bucket return the fastest recorded config —
and falls back to a VMEM-budget heuristic when nothing has been
measured.  ``BanditPAM.fit`` feeds the ledger automatically;
``benchmarks/megakernel_bench.py`` sweeps ``candidates()`` to seed it.

The ledger is in-process state (a dict), deliberately: tile timing is
device-local and a persisted cache would go stale across
driver/topology changes.  Serving processes warm it once at startup.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional, Tuple

import jax

from repro.kernels import vmem

# Reference-tile width every parity-checked streaming path is pinned to.
# MUST stay equal to repro.core.engine._EXACT_CHUNK (asserted there): the
# jnp scan chunks and the kernel grid walk share these boundaries so both
# backends accumulate per-arm sums in the same order.
REF_TILE = 512

_TM_CANDIDATES = (128, 256, 512)


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Resolved tile sizes for one streaming dispatch."""

    tm: int             # candidate-tile rows
    tb: int = REF_TILE  # reference-tile width (parity-pinned default)


def _bucket(v: int) -> int:
    """Power-of-two shape bucket: tile choice is insensitive to exact n."""
    return 1 << max(int(v) - 1, 0).bit_length()


def shape_key(n: int, d: int, k: int, device_kind: Optional[str] = None,
              backend: str = "jnp") -> Tuple:
    if device_kind is None:
        device_kind = jax.default_backend()
    return (_bucket(n), _bucket(d), _bucket(k), device_kind, backend)


# measured ledger: shape_key -> {TileConfig: best wall seconds}
_LEDGER: Dict[Tuple, Dict[TileConfig, float]] = {}


def heuristic(n: int, d: int, k: int, device_kind: Optional[str] = None,
              backend: str = "jnp") -> TileConfig:
    """VMEM-budget default: the largest ``tm`` whose streaming kernels
    fit the scoped VMEM limit, counting double-buffered operand tiles,
    output blocks and compiler scratch (``vmem.gstats_bytes``).  Where
    even the smallest ``tm`` does not fit, it is returned anyway and the
    stats backend takes the jnp walk.  On CPU the Pallas kernels run in
    interpret mode where bigger tiles only grow the emulated working
    set, so ``tm`` stays at the floor."""
    if device_kind is None:
        device_kind = jax.default_backend()
    if backend == "pallas" and device_kind == "cpu":
        return TileConfig(tm=_TM_CANDIDATES[0])
    tm = _TM_CANDIDATES[0]
    for cand in _TM_CANDIDATES:
        if cand > max(int(n), 1):
            break
        if vmem.fits(vmem.gstats_bytes(cand, REF_TILE, d, k)):
            tm = cand
    return TileConfig(tm=tm)


def candidates(n: int, d: int, k: int, device_kind: Optional[str] = None,
               backend: str = "jnp") -> Iterable[TileConfig]:
    """Sweepable configs for ``observe()`` feeders (benchmarks, warmup)."""
    base = heuristic(n, d, k, device_kind, backend)
    seen = []
    for tm in _TM_CANDIDATES:
        if (tm <= max(int(n), 1) * 2
                and vmem.fits(vmem.gstats_bytes(tm, base.tb, d, k))):
            cfg = dataclasses.replace(base, tm=tm)
            if cfg not in seen:
                seen.append(cfg)
    return seen or [base]


def observe(n: int, d: int, k: int, config: TileConfig,
            wall_by_phase: Dict[str, float],
            device_kind: Optional[str] = None,
            backend: str = "jnp") -> None:
    """Record a measured wall (sum of the distance-phase walls) for the
    config that produced it.  Best-of is kept per config so noisy reps
    only ever improve the estimate."""
    wall = float(sum(wall_by_phase.get(p, 0.0)
                     for p in ("build", "swap", "loss", "stream")))
    if wall <= 0.0:
        return
    key = shape_key(n, d, k, device_kind, backend)
    best = _LEDGER.setdefault(key, {})
    best[config] = min(best.get(config, float("inf")), wall)


def resolve_tile_config(n: int, d: int, k: int,
                        device_kind: Optional[str] = None,
                        backend: str = "jnp") -> TileConfig:
    """Measured-best config for the shape bucket, else the heuristic."""
    key = shape_key(n, d, k, device_kind, backend)
    measured = _LEDGER.get(key)
    if measured:
        return min(measured.items(), key=lambda kv: kv[1])[0]
    return heuristic(n, d, k, device_kind, backend)


def ledger_snapshot() -> Dict[Tuple, Dict[TileConfig, float]]:
    """Copy of the measured ledger (benchmark/CI introspection)."""
    return {k: dict(v) for k, v in _LEDGER.items()}


def clear_ledger() -> None:
    _LEDGER.clear()
