"""graphcheck entrypoint registry — every hot compiled program, by name.

Each :class:`GraphSpec` names one compiled program the repo dispatches on
a hot path and knows how to *lower* it at two shape points:

* ``build()`` — the canonical SMALL shapes (``N`` = 640 rows, one step
  past the ``_EXACT_CHUNK`` = 512 reference tile so every streaming walk
  actually loops).  The jaxpr rules (GRC002/3/4/6), the donation check
  (GRC005, read off the lowered StableHLO) and the golden op-census
  fingerprint all run here — tracing is cheap, so the full registry is
  analysed on every run.
* ``build_big()`` — the declared budget shapes (GRC001 only): the
  program is lowered AND compiled so ``memory_analysis()`` can bound the
  peak temp against the ``budgets.py`` declaration.  Only entrypoints
  with a ``budget`` key pay this.

The registry is the contract surface: adding a hot dispatch to the repo
means adding a spec here (the self-check test asserts the known driver
names stay registered), and every declared number — collective census,
donated leaf count, narrowing-convert allowance, byte budget — is data
that the rules enforce against the *compiled artifact*, not the source.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from . import budgets

__all__ = ["GraphSpec", "registry", "N", "D", "K", "B", "WIDTH", "BF", "T"]

# Canonical small shapes.  N sits one step past the 512-row reference
# tile so fori/dynamic-slice streaming walks take >1 step; every other
# axis (k, d, batch width, ring width, fit count) stays far below N so a
# materialised [n, n]-class block is unambiguous to GRC002.
N, D, K = 640, 8, 8
B = 32            # bandit batch (reference columns per round)
W_ROUNDS = 2      # PIC ring round capacity at registry shapes
WIDTH = W_ROUNDS * B
BF = 2            # batched multi-fit lane count
T = 3             # batched multi-fit max_swaps
RB = -(-N // B) * B


@dataclasses.dataclass(frozen=True)
class GraphSpec:
    """One registered compiled program + its declared contracts."""

    name: str
    # () -> (lowerable fn, positional args, kwargs incl. static argnames)
    build: Callable[[], Tuple]
    # {"streaming", "hot", "kernel", "batch", "sharded"}
    tags: frozenset
    # the dataset axis at registry shapes: GRC002 flags any intermediate
    # whose aval has >= 2 axes of at least this extent
    n: int = N
    # declared collective census over the whole jaxpr (GRC003);
    # absent keys mean zero
    collectives: Mapping[str, int] = dataclasses.field(default_factory=dict)
    # number of array leaves that must carry a tf.aliasing_output
    # attribute in the lowered program (GRC005); 0 = nothing donated
    donated_leaves: int = 0
    # audited narrowing float->float converts (GRC006); 0 = none allowed
    allowed_narrowing: int = 0
    # budgets.py key (GRC001); None = no compiled-memory gate
    budget: Optional[str] = None
    # () -> (fn, args, kwargs) at the budget shapes; required iff budget
    build_big: Optional[Callable[[], Tuple]] = None


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _bool(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bool_)


def _driver_statics(**over):
    kw = dict(backend="jnp", metric="l2", batch_size=B,
              delta=1.0 / (1000.0 * N), sampling="permutation",
              baseline="none", k=K, mode="none", free_rounds=0)
    kw.update(over)
    return kw


def _pic_cache_avals(bf: Optional[int] = None):
    from repro.core.pic_cache import PicCache
    if bf is None:
        return PicCache(cols=_f32(N, WIDTH), hw=_i32(), fresh_pos=_u32())
    return PicCache(cols=_f32(bf, N, WIDTH), hw=_i32(bf),
                    fresh_pos=_u32(bf))


# -- core drivers -----------------------------------------------------------

def _build_fused(mode: str):
    def build():
        from repro.core import banditpam as bp
        kw = _driver_statics(mode=mode)
        if mode == "pic":
            args = (_f32(N, D), _u32(K, 2), _pic_cache_avals(), None,
                    _i32(N))
        else:
            args = (_f32(N, D), _u32(K, 2), None, None, None)
        return bp._build_fused, args, kw
    return build


def _swap_iter(mode: str):
    def build():
        from repro.core import banditpam as bp
        kw = _driver_statics(mode=mode, delta=1.0 / (1000.0 * K * N),
                             early_stop=False)
        if mode == "pic":
            carry = (_f32(K * N), _f32(K * N), _i32(), _f32(N), _f32(N),
                     _i32(N))
            args = (_f32(N, D), _i32(K), _bool(N), _u32(2),
                    _pic_cache_avals(), None, _i32(N), _i32(WIDTH),
                    _f32(WIDTH), carry, _f32())
        else:
            args = (_f32(N, D), _i32(K), _bool(N), _u32(2), None, None,
                    None, None, None, None, _f32())
        return bp._swap_iter_jit, args, kw
    return build


def _build_batch():
    from repro.core import banditpam as bp
    kw = _driver_statics(mode="pic", delta=None)
    args = (_f32(BF, N, D), _u32(BF, K, 2), _pic_cache_avals(BF),
            _i32(BF, RB), _f32(BF, RB), _bool(BF, N), _i32(BF),
            _f32(BF))
    return bp._build_batch, args, kw


def _swap_batch():
    from repro.core import banditpam as bp
    kw = _driver_statics(mode="pic", delta=None, early_stop=False,
                         max_swaps=T)
    args = (_f32(BF, N, D), _i32(BF, K), _bool(BF, N), _u32(BF, T, 2),
            _pic_cache_avals(BF), _i32(BF, WIDTH), _f32(BF, WIDTH),
            _i32(BF, RB), _f32(BF, RB), _bool(BF, N), _i32(BF), _f32(BF))
    return bp._swap_batch, args, kw


# -- engine streaming helpers ----------------------------------------------

def _engine_fn(name: str, big: bool = False):
    import numpy as np  # noqa: F401  (kept for symmetry with _dist)
    from repro.core import engine
    n, d, k = ((budgets.N_BIG, budgets.D_BIG, budgets.K_BIG) if big
               else (N, D, K))
    if name == "total_loss":
        fn = jax.jit(functools.partial(engine.total_loss, metric="l2"))
        return fn, (_f32(n, d), _i32(k)), {}
    if name == "medoid_cache":
        fn = jax.jit(functools.partial(engine.medoid_cache, metric="l2"))
        return fn, (_f32(n, d), _i32(k)), {}
    be = engine.get_stats_backend("jnp")
    if name == "exact_build_means":
        fn = jax.jit(lambda data, dn: engine.exact_build_means(
            be, data, dn, metric="l2"))
        return fn, (_f32(n, d), _f32(n)), {}
    assert name == "exact_swap_means"
    fn = jax.jit(lambda data, d1, d2, a: engine.exact_swap_means(
        be, data, d1, d2, a, k, metric="l2"))
    return fn, (_f32(n, d), _f32(n), _f32(n), _i32(n)), {}


# -- pallas streaming kernels (interpret mode off-TPU) ----------------------

def _stream_kernel(name: str, big: bool = False):
    from repro.kernels import ops
    n, d = (budgets.N_BIG, budgets.D_BIG) if big else (N, D)
    m = 256 if big else 64
    if name == "build":
        fn = jax.jit(lambda x, y, dn, w, lg: ops.stream_build_g_stats(
            x, y, dn, w, lg, metric="l2sq", interpret=True))
        return fn, (_f32(m, d), _f32(n, d), _f32(n), _f32(n), _f32(n)), {}
    if name == "swap":
        fn = jax.jit(lambda x, y, d1, d2, a, w, lg: ops.stream_swap_g_stats(
            x, y, d1, d2, a, w, K, lg, metric="l2sq", interpret=True))
        return fn, (_f32(m, d), _f32(n, d), _f32(n), _f32(n), _i32(n),
                    _f32(n), _f32(n)), {}
    assert name == "top2"
    fn = jax.jit(lambda x, med: ops.stream_top2(
        x, med, metric="l2sq", interpret=True))
    return fn, (_f32(n, d), _f32(K, d)), {}


# -- serving closures -------------------------------------------------------

def _predict_fn(big: bool = False):
    from repro.api import predict
    rows = budgets.ROWS_PREDICT if big else 256
    k, d = (budgets.K_BIG, budgets.D_BIG) if big else (K, D)
    fn = predict.get_predict_fn(k, d, "l2", "jnp", rows)
    return fn, (_f32(rows, d), _f32(k, d)), {}


def _assign_fn(big: bool = False):
    from repro.api import predict
    rows = budgets.ROWS_ASSIGN if big else 1024
    k, d = (budgets.K_BIG, budgets.D_BIG) if big else (K, D)
    fn = predict.get_assign_fn(k, d, "l2", "jnp", rows)
    return fn, (_f32(rows, d), _f32(k, d)), {}


# -- sharded phases ---------------------------------------------------------

def _dist_phase(which: str):
    def build():
        import numpy as np
        from repro.core.distributed import DistributedBanditPAM, default_mesh
        from repro.core.engine import (get_stats_backend,
                                       resolve_stats_backend)
        est = DistributedBanditPAM(K, default_mesh(), batch_size=B,
                                   reuse="pic", cache_width=WIDTH, seed=0)
        be = get_stats_backend(resolve_stats_backend(est.backend,
                                                     est.metric))
        rng = np.random.default_rng(0)
        data = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
        data_sh = est._shard_data(data)
        key = jax.random.PRNGKey(0)
        key, ckey = jax.random.split(key)
        lperm, lw, pidx_g, pw_g, cache, w_r = est._pic_layout(N, ckey)
        if which == "build":
            fn = est._make_build_phase(be, N, 1.0 / (1000.0 * N), w_r)
            subs = jnp.stack([jax.random.PRNGKey(i) for i in range(K)])
            args = (data, data_sh, jax.random.PRNGKey(7), subs, lperm,
                    lw, pidx_g, pw_g, cache)
        else:
            fn = est._make_swap_iter(be, N, 1.0 / (1000.0 * K * N), w_r)
            med = jnp.arange(K, dtype=jnp.int32) * (N // K)
            mask = jnp.zeros((N,), jnp.bool_).at[med].set(True)
            args = (data, data_sh, med, mask, jax.random.PRNGKey(3),
                    jax.random.PRNGKey(4), lperm, lw, pidx_g, pw_g,
                    cache, None)
        return fn, args, {}
    return build


# -- the registry -----------------------------------------------------------

_HOT = frozenset({"hot"})
_STREAM = frozenset({"hot", "streaming"})
_KERNEL = frozenset({"hot", "streaming", "kernel"})
_BATCH = frozenset({"hot", "streaming", "batch"})
_SHARDED = frozenset({"hot", "streaming", "sharded"})

# The sharded phases run one fori_loop-resident shard_map with three
# moment reductions (sums / sqsums / cross-term) — the census is 3 psums
# through 1 shard_map site, NOT one psum per phase: FastPAM1 sharing
# needs all three moments per round (docs/design.md #4/#10).  BUILD adds
# one collective-free replicated shard_map for its d_near row (a Mosaic
# kernel cannot sit outside a shard_map in a partitioned program); the
# exact-fallback passes, wrapped the same way, are never traced under
# the permutation sampling of reuse="pic".
_SMAP_CENSUS = {"psum": 3, "shard_map": 1}
_BUILD_CENSUS = {"psum": 3, "shard_map": 2}


def registry() -> Tuple[GraphSpec, ...]:
    """The shipped entrypoint set, one spec per hot compiled program."""
    return (
        GraphSpec("core._build_fused[none]", _build_fused("none"), _STREAM),
        GraphSpec("core._build_fused[pic]", _build_fused("pic"), _STREAM,
                  donated_leaves=3,
                  budget="core._build_fused[pic]",
                  build_big=_big_driver_build),
        GraphSpec("core._swap_iter[none]", _swap_iter("none"), _STREAM),
        GraphSpec("core._swap_iter[pic]", _swap_iter("pic"), _STREAM,
                  donated_leaves=9,
                  budget="core._swap_iter[pic]",
                  build_big=_big_driver_swap),
        GraphSpec("core._build_batch[pic]", _build_batch, _BATCH),
        GraphSpec("core._swap_batch[pic]", _swap_batch, _BATCH),
        GraphSpec("engine.total_loss",
                  lambda: _engine_fn("total_loss"), _STREAM,
                  budget="engine.total_loss",
                  build_big=lambda: _engine_fn("total_loss", big=True)),
        GraphSpec("engine.medoid_cache",
                  lambda: _engine_fn("medoid_cache"), _STREAM,
                  budget="engine.medoid_cache",
                  build_big=lambda: _engine_fn("medoid_cache", big=True)),
        GraphSpec("engine.exact_build_means",
                  lambda: _engine_fn("exact_build_means"), _STREAM,
                  budget="engine.exact_build_means",
                  build_big=lambda: _engine_fn("exact_build_means",
                                               big=True)),
        GraphSpec("engine.exact_swap_means",
                  lambda: _engine_fn("exact_swap_means"), _STREAM,
                  budget="engine.exact_swap_means",
                  build_big=lambda: _engine_fn("exact_swap_means",
                                               big=True)),
        GraphSpec("kernels.stream_build_g_stats",
                  lambda: _stream_kernel("build"), _KERNEL,
                  budget="kernels.stream_build_g_stats",
                  build_big=lambda: _stream_kernel("build", big=True)),
        GraphSpec("kernels.stream_swap_g_stats",
                  lambda: _stream_kernel("swap"), _KERNEL,
                  budget="kernels.stream_swap_g_stats",
                  build_big=lambda: _stream_kernel("swap", big=True)),
        GraphSpec("kernels.stream_top2",
                  lambda: _stream_kernel("top2"), _KERNEL,
                  budget="kernels.stream_top2",
                  build_big=lambda: _stream_kernel("top2", big=True)),
        # get_predict_fn RETURNS the [rows, k] block — materialising it is
        # the product, so no "streaming" tag; the budget bounds the temps
        # AROUND that block instead of forbidding it.
        GraphSpec("api.get_predict_fn", _predict_fn, _HOT,
                  budget="api.get_predict_fn",
                  build_big=lambda: _predict_fn(big=True)),
        GraphSpec("api.get_assign_fn", _assign_fn, _STREAM,
                  budget="api.get_assign_fn",
                  build_big=lambda: _assign_fn(big=True)),
        GraphSpec("dist.build_phase[pic]", _dist_phase("build"), _SHARDED,
                  collectives=_BUILD_CENSUS),
        GraphSpec("dist.swap_iter[pic]", _dist_phase("swap"), _SHARDED,
                  collectives=_SMAP_CENSUS),
    )


def _big_driver_build():
    from repro.core import banditpam as bp
    n, d, k = budgets.N_DRIVER, budgets.D_DRIVER, budgets.K_DRIVER
    width = budgets.WIDTH_DRIVER
    from repro.core.pic_cache import PicCache
    cache = PicCache(cols=_f32(n, width), hw=_i32(), fresh_pos=_u32())
    kw = dict(backend="jnp", metric="l2", batch_size=B,
              delta=1.0 / (1000.0 * n), sampling="permutation",
              baseline="none", k=k, mode="pic", free_rounds=0)
    return bp._build_fused, (_f32(n, d), _u32(k, 2), cache, None,
                             _i32(n)), kw


def _big_driver_swap():
    from repro.core import banditpam as bp
    n, d, k = budgets.N_DRIVER, budgets.D_DRIVER, budgets.K_DRIVER
    width = budgets.WIDTH_DRIVER
    from repro.core.pic_cache import PicCache
    cache = PicCache(cols=_f32(n, width), hw=_i32(), fresh_pos=_u32())
    carry = (_f32(k * n), _f32(k * n), _i32(), _f32(n), _f32(n), _i32(n))
    kw = dict(backend="jnp", metric="l2", batch_size=B,
              delta=1.0 / (1000.0 * k * n), sampling="permutation",
              baseline="none", k=k, mode="pic", free_rounds=0,
              early_stop=False)
    return bp._swap_iter_jit, (_f32(n, d), _i32(k), _bool(n), _u32(2),
                               cache, None, _i32(n), _i32(width),
                               _f32(width), carry, _f32()), kw


def by_name() -> Dict[str, GraphSpec]:
    return {s.name: s for s in registry()}
