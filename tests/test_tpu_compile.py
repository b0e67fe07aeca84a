"""Compile the main-path Pallas kernels for one described TPU v5e chip.

Interpret mode runs a kernel body in Python and cannot see what the
chip's compiler refuses: a slice it cannot lower, or more scoped VMEM
than a kernel may use.  These tests lower each kernel through its
``repro.kernels.ops`` wrapper for a ``v5e:2x2`` topology that is
described, not attached, and compile it with the TPU compiler installed
beside JAX.  They cover every kernel of the fit and serving paths at the
paper's widths (d=784 MNIST, d=1000 scRNA) for each kernel metric, and
the largest shapes the dispatch rules admit (``ops.DK_MAX``,
``ops.gstats_fit`` behind ``PallasStatsBackend._stream_ok`` and
``tuning.heuristic``), so that the VMEM accounting in
``repro.kernels.vmem`` and the compiler are checked against each other.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.  Nothing runs, so nothing here is a chip measurement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import tuning
from repro.kernels import ops, vmem

K = 5
M = 2048            # candidate rows
BATCH = 100         # one bandit round's references (the default batch)
METRICS = ("l2", "cosine", "l1")
WIDTHS = (784, 1000)
ONE_SHOT_TM = 128   # PallasStatsBackend.tm


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 (four chips), with the persistent compilation
    cache off (a compile for a described chip is written to it but
    cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _one_shot(kind, metric):
    if kind == "build_g":
        return lambda x, y, dn, w: ops.build_g_stats(
            x, y, dn, w, w, metric=metric, tm=ONE_SHOT_TM, interpret=False)
    return lambda x, y, dn, w: ops.swap_g_stats(
        x, y, dn, dn, (w > 0).astype(jnp.int32), w, K, w, metric=metric,
        tm=ONE_SHOT_TM, interpret=False)


def _stream(kind, metric, tm, tb=tuning.REF_TILE):
    if kind == "stream_build":
        return lambda x, y, dn: ops.stream_build_g_stats(
            x, y, dn, metric=metric, tm=tm, tb=tb, interpret=False)
    if kind == "stream_swap":
        return lambda x, y, dn: ops.stream_swap_g_stats(
            x, y, dn, dn, (dn > 0).astype(jnp.int32), None, K,
            metric=metric, tm=tm, tb=tb, interpret=False)
    return lambda x, y, dn: ops.stream_top2(
        x, y[:K], metric=metric, tm=tm, interpret=False)


def _compile_stream(chip, kind, metric, d, tm):
    _compile(chip, _stream(kind, metric, tm), (M, d), (M, d), (M,))


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("metric", METRICS)
def test_pairwise(chip, metric, d):
    _compile(chip, lambda x, y: ops.pairwise_distance(
        x, y, metric=metric, interpret=False), (M, d), (BATCH, d))


@pytest.mark.parametrize("kind", ["build_g", "swap_g"])
@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("metric", METRICS)
def test_one_shot_gstats(chip, metric, d, kind):
    assert ops.gstats_fit(ONE_SHOT_TM, BATCH, d, K)
    _compile(chip, _one_shot(kind, metric), (M, d), (BATCH, d), (BATCH,),
             (BATCH,))


@pytest.mark.parametrize("b", [BATCH, ops.CACHE_B_MAX])
def test_swap_g_from_cache(chip, b):
    assert ops.cached_fit(ONE_SHOT_TM, b, K)
    _compile(chip, lambda dxy, dn: ops.swap_g_stats_cached(
        dxy, dn, dn, (dn > 0).astype(jnp.int32), dn, K, dn,
        tm=ONE_SHOT_TM, interpret=False), (M, b), (b,))


@pytest.mark.parametrize("kind", ["stream_build", "stream_swap",
                                  "stream_top2"])
@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("metric", METRICS)
def test_stream_at_paper_widths(chip, metric, d, kind):
    """At the tile the tuner picks on a TPU for a paper-scale n."""
    tm = tuning.heuristic(70_000, d, K, device_kind="tpu",
                          backend="pallas").tm
    assert ops.gstats_fit(tm, tuning.REF_TILE, d, K)
    _compile_stream(chip, kind, metric, d, tm)


def _widest(tm):
    return vmem.max_feature_width(
        lambda d: vmem.gstats_bytes(tm, tuning.REF_TILE, d, K))


@pytest.mark.parametrize("kind", ["stream_build", "stream_swap",
                                  "stream_top2"])
@pytest.mark.parametrize("metric", ["l2", "l1"])
@pytest.mark.parametrize("tm", [128, 256, 512])
def test_stream_at_widest_admitted(chip, tm, metric, kind):
    """The widest d each tm is admitted at: the dispatch rule's edge,
    which the tuner picks and the compiler must accept."""
    d = _widest(tm)
    assert ops.gstats_fit(tm, tuning.REF_TILE, d, K)
    assert not ops.gstats_fit(tm, tuning.REF_TILE, d + 128, K)
    assert tuning.heuristic(70_000, d, K, device_kind="tpu",
                            backend="pallas").tm == tm
    _compile_stream(chip, kind, metric, d, tm)


@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_pairwise_at_dk_max(chip, metric):
    """``pairwise_distance`` keeps d <= DK_MAX in one pass, with several
    reference tiles (so both operand tiles are double-buffered)."""
    assert vmem.fits(vmem.pairwise_bytes(128, 128, ops.DK_MAX))
    assert not vmem.fits(vmem.pairwise_bytes(128, 128, ops.DK_MAX + 128))
    _compile(chip, lambda x, y: ops.pairwise_distance(
        x, y, metric=metric, interpret=False), (M, ops.DK_MAX),
        (M, ops.DK_MAX))


def test_pairwise_past_dk_max_splits_features(chip):
    d = 2 * ops.DK_MAX + 300
    _compile(chip, lambda x, y: ops.pairwise_distance(
        x, y, metric="l2", interpret=False), (M, d), (BATCH, d))


@pytest.mark.parametrize("kind", ["build_g", "swap_g"])
def test_one_shot_at_widest_admitted(chip, kind):
    d = vmem.max_feature_width(
        lambda d: vmem.gstats_bytes(ONE_SHOT_TM, BATCH, d, K))
    assert ops.gstats_fit(ONE_SHOT_TM, BATCH, d, K)
    assert not ops.gstats_fit(ONE_SHOT_TM, BATCH, d + 128, K)
    _compile(chip, _one_shot(kind, "l2"), (M, d), (BATCH, d), (BATCH,),
             (BATCH,))


@pytest.mark.parametrize("phase", ["build", "swap"])
@pytest.mark.parametrize("reuse", ["none", "pic"])
def test_sharded_fit_compiles_for_four_chips(topo, reuse, phase):
    """``banditpam_dist`` with the Pallas backend over the four chips: a
    Mosaic kernel cannot be partitioned by XLA, so every backend call of
    the sharded phases must sit inside a ``shard_map``."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import DistributedBanditPAM
    from repro.core.engine import PallasStatsBackend
    from repro.core.pic_cache import PicCache, resolve_cache_rounds

    n, d = 4096, 784
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    est = DistributedBanditPAM(K, mesh, reuse=reuse)
    be = PallasStatsBackend(interpret=False)
    s, b = est.n_shards, est.batch_size
    b_loc, n_loc = b // s, est._n_loc(n)
    r_max = -(-n_loc // b_loc)

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    data = arg((n, d), jnp.float32)
    data_sh = arg((n_loc * s, d), jnp.float32, P("data", None))
    key = arg((2,), jnp.uint32)
    if reuse == "pic":
        w = resolve_cache_rounds(r_max, b, None)
        walk = [arg((s, r_max * b_loc), dt, P("data", None))
                for dt in (jnp.int32, jnp.float32)]
        layout = [arg((r_max * b,), dt) for dt in (jnp.int32, jnp.float32)]
        cache = PicCache(cols=arg((n, s * w * b_loc), jnp.float32,
                                  P(None, "data")),
                         hw=arg((), jnp.int32), fresh_pos=arg((), jnp.uint32),
                         refresh_pos=arg((), jnp.uint32))
        carry = (arg((K * n,), jnp.float32), arg((K * n,), jnp.float32),
                 arg((), jnp.int32), arg((n,), jnp.float32),
                 arg((n,), jnp.float32), arg((n,), jnp.int32))
    else:
        w, walk, layout, cache, carry = 0, [None] * 2, [None] * 2, None, None
    if phase == "build":
        fn = est._make_build_phase(be, n, 1.0 / (1000.0 * n), w)
        args = (data, data_sh, key, arg((K, 2), jnp.uint32), *walk,
                *layout, cache)
    else:
        fn = est._make_swap_iter(be, n, 1.0 / (1000.0 * K * n), w)
        args = (data, data_sh, arg((K,), jnp.int32), arg((n,), jnp.bool_),
                key, key, *walk, *layout, cache, carry)
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
