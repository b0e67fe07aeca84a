"""The fit's tracing layer: host spans on the profiler's clock, device
scopes in the lowered programs, and the PIC ring's re-computed columns.

* ``engine.span`` / ``engine.phase`` write ``repro.*`` host spans that a
  ``jax.profiler`` trace holds, nested ``repro.fit ⊃ repro.build,
  repro.swap ⊃ repro.swap.iter``; ``FitReport.wall_by_phase`` is the
  phase span's length.
* ``jax.named_scope`` names the device stages inside the jitted phases,
  so each lowered op's location carries its stage.
* ``FitReport.refresh_evals`` counts the fresh evaluations of PIC rounds
  the bounded ring had evicted (``pic_cache.cache_advance``).
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import BanditPAM, datasets
from repro.core import banditpam as bp
from repro.core.pic_cache import PicCache, cache_advance, make_cache

SCOPES = {"build", "swap", "search", "exact", "fresh", "pic", "stats",
          "top2", "carry", "prep"}


def _traced_fit(tmp_path, est, x):
    est.fit(x)                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = est.fit(x)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.")]
    return rep, spans


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    x = datasets.mnist_like(300, seed=0)
    return _traced_fit(tmp_path_factory.mktemp("trace"),
                       BanditPAM(k=3, reuse="pic", seed=1), x)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_fit_spans_nest(traced):
    rep, spans = traced
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    (fit,), (build,), (swap,) = by["repro.fit"], by["repro.build"], \
        by["repro.swap"]
    assert _inside(build, fit) and _inside(swap, fit)
    assert build[2] <= swap[1]
    iters = by["repro.swap.iter"]
    assert len(iters) == rep.dispatches_by_phase["swap"] >= 1
    assert all(_inside(it, swap) for it in iters)
    # every host read and staging span lies inside the fit, and no span
    # name carries a source line
    assert all(_inside(s, fit) for s in spans if s[0] != "repro.fit")
    assert any(s[0].startswith("repro.stage(") for s in spans)
    assert not any(re.search(r"\.py:\d", s[0]) for s in spans)


def test_wall_by_phase_is_the_span_length(traced):
    rep, spans = traced
    for phase in ("build", "swap"):
        (s,) = [s for s in spans if s[0] == f"repro.{phase}"]
        span_s = (s[2] - s[1]) / 1e9
        wall = rep.wall_by_phase[phase]
        assert span_s <= wall + 1e-4
        assert wall - span_s < 5e-3, (phase, wall, span_s)


def _scopes_of(lowered) -> set:
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return {c for n in names for c in n.split("/")} & SCOPES


N, D, K, B, W = 256, 8, 3, 64, 2


def _avals():
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)   # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32)    # noqa: E731
    cache = PicCache(f32(N, W * B), i32(), u32(), u32())
    kw = dict(backend="pallas", metric="l2", batch_size=B, delta=1e-3,
              sampling="permutation", baseline="none", mode="pic",
              free_rounds=0)
    return f32, i32, u32, cache, kw


def test_lowered_build_carries_its_scopes():
    f32, i32, u32, cache, kw = _avals()
    low = bp._build_fused.lower(f32(N, D), u32(K, 2), cache, None, i32(N),
                                k=K, **kw)
    assert {"build", "search", "pic", "fresh", "stats",
            "prep"} <= _scopes_of(low)


def test_lowered_swap_iter_carries_its_scopes():
    f32, i32, u32, cache, kw = _avals()
    carry = (f32(K * N), f32(K * N), i32(), f32(N), f32(N), i32(N))
    low = bp._swap_iter_jit.lower(
        f32(N, D), i32(K), jax.ShapeDtypeStruct((N,), jnp.bool_), u32(2),
        cache, None, i32(N), i32(W * B), f32(W * B), carry, f32(), k=K,
        early_stop=False, **kw)
    assert {"swap", "search", "pic", "fresh", "stats", "prep", "top2",
            "carry"} <= _scopes_of(low)


def _ring(accesses, n, block, rounds):
    """Hand count of the ring: evicted-round replays, in positions."""
    hw, refresh = 0, 0
    for rnd in accesses:
        b_eff = max(0, min(block, n - rnd * block))
        if rnd < hw - rounds:
            refresh += b_eff
        hw = max(hw, rnd + 1)
    return refresh


@pytest.mark.parametrize("rounds", [1, 2, 6])
def test_cache_advance_counts_evicted_replays(rounds):
    n, block = 590, 100
    accesses = [0, 1, 2, 3, 0, 1, 2, 3, 4, 5, 0, 5, 2]
    c = make_cache(n, block, rounds)
    for rnd in accesses:
        b_eff = min(block, n - rnd * block)
        c = cache_advance(c, c.cols, jnp.int32(rnd), jnp.int32(b_eff),
                          rounds)
    assert int(c.refresh_pos) == _ring(accesses, n, block, rounds)
    assert int(c.refresh_pos) <= int(c.fresh_pos)


def test_refresh_evals_zero_when_the_ring_holds_every_round():
    x = datasets.mnist_like(600, seed=2)
    rep = BanditPAM(k=4, reuse="pic", seed=3).fit(x)
    assert rep.refresh_evals == 0
    assert BanditPAM(k=4, seed=3).fit(x).refresh_evals == 0


def test_refresh_evals_match_a_hand_count_at_one_round():
    """BUILD alone (``max_swaps=0``): every BUILD search walks rounds
    ``0 .. build_rounds[i]-1`` of the one fixed permutation, so the ring
    of one round (``cache_width=batch_size``) can be replayed by hand."""
    n, block = 600, 100
    x = datasets.mnist_like(n, seed=2)
    rep = BanditPAM(k=4, reuse="pic", seed=3, max_swaps=0,
                    cache_width=block).fit(x)
    accesses = [r for rounds in rep.build_rounds for r in range(rounds)]
    want = n * _ring(accesses, n, block, 1)
    assert want > 0
    assert rep.refresh_evals == want
    assert rep.refresh_evals <= rep.distance_evals


def test_refresh_evals_agree_across_drivers():
    """Solo fused, stepped and batched fits count the same replays (the
    batched lanes bit-match solo fits), and the full fit replays more
    than BUILD alone."""
    xs = [datasets.mnist_like(n, seed=5 + n) for n in (560, 600)]
    kw = dict(k=3, reuse="pic", cache_width=100)
    solo = [BanditPAM(seed=s, **kw).fit(x) for s, x in zip((1, 2), xs)]
    stepped = BanditPAM(seed=1, fused=False, **kw).fit(xs[0])
    batch = BanditPAM(seed=0, **kw).fit_batch(xs, seeds=[1, 2])
    assert [r.refresh_evals for r in batch] == [r.refresh_evals
                                                for r in solo]
    assert stepped.refresh_evals == solo[0].refresh_evals
    build_only = BanditPAM(seed=1, max_swaps=0, **kw).fit(xs[0])
    assert solo[0].refresh_evals > build_only.refresh_evals
    assert np.all([r.refresh_evals > 0 for r in solo])
