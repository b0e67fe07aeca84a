"""The PIC fresh rounds' aligned operand (``StatsBackend.align_rows``).

On the Pallas backend the single-device PIC programs zero-pad the data set
to the pairwise kernel's row tile and feature lanes once per program,
outside every loop, and each fresh round gathers its references from
that operand (``pic_cache.cache_read_or_write``).  The kernel sees the
same tiles as when ``ops.pairwise_distance`` padded the whole data set
on every fresh round, so fits are bit-identical to that path.

* ``align_rows`` is the pairwise wrapper's own padding on Pallas and the
  identity on jnp.
* In every lowered PIC program the data-set-shaped pad sits outside all
  ``while`` regions, once.
* Fits through the aligned operand match fits through the unaligned
  ``pairwise_distance`` call: medoids, loss and every ledger entry.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BanditPAM, datasets
from repro.core import banditpam as bp
from repro.core.engine import (JnpStatsBackend, PallasStatsBackend,
                               register_stats_backend)
from repro.core.pic_cache import PicCache
from repro.kernels import ops


# -- align_rows ---------------------------------------------------------------

@pytest.mark.parametrize("shape", [(300, 70), (256, 128), (2, 300, 70),
                                   (129, 200)])
def test_pallas_align_rows_is_the_wrappers_padding(shape):
    x = jnp.asarray(np.random.default_rng(0).normal(size=shape),
                    jnp.float32)
    got = PallasStatsBackend(interpret=True).align_rows(x)
    want = ops._pad_to(ops._pad_to(x, x.ndim - 1, 128), x.ndim - 2, 128)
    assert got.shape == want.shape
    assert got.shape[-2] % 128 == 0 and got.shape[-1] % 128 == 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pallas_align_rows_leaves_data_past_one_kernel_pass():
    x = jnp.ones((8, ops.DK_MAX + 1), jnp.float32)
    assert PallasStatsBackend(interpret=True).align_rows(x) is x


def test_jnp_align_rows_is_the_input():
    x = jnp.ones((300, 70), jnp.float32)
    assert JnpStatsBackend().align_rows(x) is x


@pytest.mark.parametrize("metric", ["l2", "l2sq", "l1", "cosine"])
def test_pairwise_on_the_aligned_operand_is_bit_identical(metric):
    x = jnp.asarray(datasets.mnist_like(300, seed=3, d=70), jnp.float32)
    idx = jnp.asarray([5, 299, 0, 17, 128], jnp.int32)
    be = PallasStatsBackend(interpret=True)
    xp = be.align_rows(x)
    got = be.pairwise(xp, xp[idx], metric=metric, rows=x.shape[0])
    want = be.pairwise(x, x[idx], metric=metric)
    assert got.shape == want.shape == (300, 5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- lowered programs ---------------------------------------------------------
#
# N and D are no tile multiples.  D=200 pads to 256 lanes, apart from the
# 128 lanes of the ring (W·B = 128 columns) and of each round's [N, B]
# stats block, so a [384, 256] pad can only be the data set's.

N, D, K, B, W, BF, T = 300, 200, 3, 64, 2, 2, 2
RB = -(-N // B) * B


def _funcs(text: str) -> dict:
    """Lowered module → {function name: body lines}."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public|private) @([\w.$-]+)\(", line)
        if m:
            name = m.group(1)
            out[name] = []
        elif name:
            out[name].append(line)
    return out


def _pads(text: str, shape: str):
    """(outside, inside): ``stablehlo.pad`` ops whose result is
    ``tensor<{shape}xf32>``, reached from ``main`` outside and inside a
    ``while`` region, following every call."""
    fns = _funcs(text)
    memo = {}

    def walk(fn, looped):
        if (fn, looped) not in memo:
            count = [0, 0]
            depth, whiles = 0, []
            for line in fns[fn]:
                inside = looped or bool(whiles)
                if "stablehlo.pad" in line and f"-> tensor<{shape}xf32>" in line:
                    count[inside] += 1
                for callee in re.findall(r"call @([\w.$-]+)\(", line):
                    sub = walk(callee, inside)
                    count[0] += sub[0]
                    count[1] += sub[1]
                if "stablehlo.while" in line:
                    whiles.append(depth)
                depth += line.count("{") - line.count("}")
                if whiles and depth == whiles[-1] and line.strip() == "}":
                    whiles.pop()
            memo[(fn, looped)] = tuple(count)
        return memo[(fn, looped)]

    return walk("main", False)


def _lowered(program: str, backend: str):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)   # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32)    # noqa: E731
    bl = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)      # noqa: E731
    kw = dict(backend=backend, metric="l2", batch_size=B, delta=1e-3,
              sampling="permutation", baseline="none", mode="pic",
              free_rounds=0)
    ring = PicCache(f32(N, W * B), i32(), u32(), u32())
    rings = PicCache(f32(BF, N, W * B), i32(BF), u32(BF), u32(BF))
    carry = (f32(K * N), f32(K * N), i32(), f32(N), f32(N), i32(N))
    if program == "_build_fused":
        return bp._build_fused.lower(f32(N, D), u32(K, 2), ring, None,
                                     i32(N), k=K, **kw)
    if program == "_swap_iter_jit":
        return bp._swap_iter_jit.lower(
            f32(N, D), i32(K), bl(N), u32(2), ring, None, i32(N),
            i32(W * B), f32(W * B), carry, f32(), k=K, early_stop=False,
            **kw)
    if program == "_build_step_jit":
        return bp._build_step_jit.lower(f32(N, D), f32(N), bl(N), u32(2),
                                        ring, None, i32(N), **kw)
    if program == "_swap_search_jit":
        return bp._swap_search_jit.lower(
            f32(N, D), f32(N), f32(N), i32(N), bl(N), u32(2), ring, None,
            i32(N), None, None, 0, k=K, early_stop=False, **kw)
    if program == "_build_batch":
        return bp._build_batch.lower(
            f32(BF, N, D), u32(BF, K, 2), rings, i32(BF, RB), f32(BF, RB),
            bl(BF, N), i32(BF), f32(BF), k=K, **kw)
    assert program == "_swap_batch"
    return bp._swap_batch.lower(
        f32(BF, N, D), i32(BF, K), bl(BF, N), u32(BF, T, 2), rings,
        i32(BF, W * B), f32(BF, W * B), i32(BF, RB), f32(BF, RB),
        bl(BF, N), i32(BF), f32(BF), k=K, early_stop=False, max_swaps=T,
        **kw)


PROGRAMS = ["_build_fused", "_swap_iter_jit", "_build_step_jit",
            "_swap_search_jit", "_build_batch", "_swap_batch"]


@pytest.mark.parametrize("program", PROGRAMS)
def test_data_set_is_padded_once_outside_every_loop(program):
    text = _lowered(program, "pallas").as_text()
    lead = f"{BF}x" if program in ("_build_batch", "_swap_batch") else ""
    assert _pads(text, f"{lead}384x256") == (1, 0)
    assert _pads(text, f"{lead}300x256") == (1, 0)
    # no lane of a batched program pads its own slice again
    assert _pads(text, "384x256")[1] == 0


@pytest.mark.parametrize("program", ["_build_fused", "_swap_iter_jit"])
def test_jnp_programs_pad_no_data_set(program):
    text = _lowered(program, "jnp").as_text()
    assert _pads(text, "384x256") == (0, 0)
    assert _pads(text, "300x256") == (0, 0)


# -- fits ---------------------------------------------------------------------

class _UnalignedPallas(PallasStatsBackend):
    """The Pallas backend with the data set left unaligned: every fresh
    PIC round pads it inside ``ops.pairwise_distance``."""

    name = "pallas-unaligned"

    def align_rows(self, data):
        return data


register_stats_backend("pallas-unaligned", _UnalignedPallas())


def _fit(x, backend, phase, metric):
    # A two-round ring makes later searches re-compute evicted rounds, so
    # fresh rounds run in both phases.
    est = BanditPAM(k=3, metric=metric, batch_size=32, reuse="pic",
                    cache_width=64, seed=2, backend=backend,
                    max_swaps=0 if phase == "build" else 3)
    if phase == "build":
        return est.fit(x)
    return est.fit(x, warm_start=np.asarray([0, 1, 2]))


@pytest.mark.parametrize("n", [256, 300])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("phase", ["build", "swap"])
def test_fit_matches_the_unaligned_kernel_path(phase, metric, n):
    x = datasets.mnist_like(n, seed=4, d=70)
    got = _fit(x, "pallas", phase, metric)
    want = _fit(x, "pallas-unaligned", phase, metric)
    np.testing.assert_array_equal(got.medoids, want.medoids)
    assert got.loss == want.loss
    assert got.distance_evals == want.distance_evals
    assert got.evals_by_phase == want.evals_by_phase
    assert got.refresh_evals == want.refresh_evals
    assert got.refresh_evals > 0
    assert got.swap_history == want.swap_history
    assert (len(got.swap_history) > 0) == (phase == "swap")
