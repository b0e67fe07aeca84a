"""Distance registry for k-medoids.

All functions compute *pairwise* dissimilarities between a target block
``x: [m, d]`` and a reference block ``y: [r, d]`` and return ``[m, r]``.

The k-medoids problem (paper Eq. 1/3) places no requirements on ``d`` —
it need not be symmetric, positive, or satisfy the triangle inequality —
so the registry is open: ``register_metric`` accepts any ``[m,d]x[r,d]->[m,r]``
callable, and ``resolve_metric`` (what the ``repro.api`` facade calls)
additionally accepts a raw callable (auto-registered under a derived name)
or the string ``"precomputed"``.

``"precomputed"`` serves a caller-supplied ``[n, n]`` dissimilarity matrix
— the Eq. 1/3 formulation explicitly permits arbitrary dissimilarities, so
structured objects (the paper's code-submission trees under tree-edit
distance, say) cluster through the exact same solver stack.  Every solver
here only ever touches data through row indexing and ``get_metric``
blocks, so a matrix lookup can impersonate a metric: ``attach_index``
appends each row's own index as one extra feature column, and the
registered ``"precomputed"`` metric recovers ``D[I, J]`` for a block pair
by slicing the x-rows (which carry full D rows) at the y-rows' index
column.  Zero distance recomputation, identical solver code paths.

The MXU-friendly metrics (``l2``, ``l2sq``, ``cosine``) are expressed as a
single matmul plus rank-1 corrections so both the jnp path (here) and the
Pallas path (``repro.kernels``) hit the systolic array.  ``l1`` is
bandwidth-bound and is evaluated in reference-chunks to bound the
``[m, chunk, d]`` intermediate.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

Metric = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]

_REGISTRY: Dict[str, Metric] = {}

# Keep the [m, chunk, d] L1 intermediate under ~2**24 elements.
_L1_CHUNK_ELEMS = 1 << 24

# Precision of every f32 matmul in the engine.  On a TPU the default is
# one bf16 pass, which moves l2 distances by up to ~1e-1 at d=784 and
# flips nearest-medoid decisions (measured on a v5e); HIGHEST keeps them
# f32-exact.  On the CPU both give the same bits.
EXACT = jax.lax.Precision.HIGHEST


def register_metric(name: str, fn: Metric) -> None:
    _REGISTRY[name] = fn


def get_metric(name: str) -> Metric:
    if name not in _REGISTRY:
        raise KeyError(f"unknown metric {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_metrics():
    return sorted(_REGISTRY)


def l2sq(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Squared Euclidean distance via ||x||^2 + ||y||^2 - 2 x.y (MXU-shaped)."""
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    xy = jnp.matmul(x, y.T, precision=EXACT)
    return jnp.maximum(xx + yy - 2.0 * xy, 0.0)


def l2(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(l2sq(x, y))


def cosine(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Cosine *distance* 1 - cos_sim, safe at zero vectors."""
    xn = x * jax.lax.rsqrt(jnp.maximum(jnp.sum(x * x, axis=-1, keepdims=True), 1e-30))
    yn = y * jax.lax.rsqrt(jnp.maximum(jnp.sum(y * y, axis=-1, keepdims=True), 1e-30))
    return 1.0 - jnp.matmul(xn, yn.T, precision=EXACT)


def l1(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Manhattan distance, chunked over references to bound memory."""
    m, d = x.shape
    r = y.shape[0]
    chunk = max(1, min(r, _L1_CHUNK_ELEMS // max(1, m * d)))
    if chunk >= r:
        return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)

    n_chunks = -(-r // chunk)
    pad = n_chunks * chunk - r
    y_pad = jnp.pad(y, ((0, pad), (0, 0)))
    y_chunks = y_pad.reshape(n_chunks, chunk, d)

    def one(yc):
        return jnp.sum(jnp.abs(x[:, None, :] - yc[None, :, :]), axis=-1)

    out = jax.lax.map(one, y_chunks)            # [n_chunks, m, chunk]
    out = jnp.moveaxis(out, 0, 1).reshape(m, n_chunks * chunk)
    return out[:, :r]


# ---------------------------------------------------------------------------
# Precomputed dissimilarities
# ---------------------------------------------------------------------------

# f32 holds integers exactly up to 2**24, which bounds the index column.
_MAX_PRECOMPUTED_N = 1 << 24


def attach_index(dissim) -> jnp.ndarray:
    """Prepare an ``[n, n]`` dissimilarity matrix for ``metric="precomputed"``:
    append each row's own index as a trailing feature column, so row blocks
    stay self-describing under the index-only data access of the solvers."""
    d = jnp.asarray(dissim, jnp.float32)
    if d.ndim != 2 or d.shape[0] != d.shape[1]:
        raise ValueError(f'metric="precomputed" expects a square [n, n] '
                         f"dissimilarity matrix, got shape {d.shape}")
    n = d.shape[0]
    if n >= _MAX_PRECOMPUTED_N:
        raise ValueError(f"precomputed index column is exact only for "
                         f"n < {_MAX_PRECOMPUTED_N}, got n={n}")
    idx = jnp.arange(n, dtype=jnp.float32)[:, None]
    return jnp.concatenate([d, idx], axis=1)


def precomputed(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Lookup 'metric' over ``attach_index``-augmented data: x rows carry
    ``D[i, :]``, the y rows' trailing column carries ``j`` — the pairwise
    block is a pure gather ``D[I, J]``.

    On eager (non-traced) calls the index column is validated, so passing
    a raw, un-augmented matrix to a legacy entrypoint fails loudly at the
    first eager distance call instead of silently gathering garbage
    (inside jit the column is a tracer and the check is skipped — the
    facade routes everything through ``attach_index`` anyway)."""
    col = y[:, -1]
    if not isinstance(col, jax.core.Tracer):
        cv = np.asarray(col)
        if cv.size and (cv.min() < 0 or cv.max() > x.shape[1] - 2
                        or np.any(cv != np.round(cv))):
            raise ValueError(
                'metric="precomputed" data must be routed through '
                "attach_index() (the trailing column must hold row "
                "indices); got non-index values — pass the raw [n, n] "
                "matrix to repro.api.KMedoids, or call attach_index "
                "yourself before the legacy entrypoints")
    j = col.astype(jnp.int32)
    return jnp.take(x[:, :-1], j, axis=1)


def resolve_metric(metric) -> str:
    """Normalise a user-facing ``metric`` argument to a registered name.

    Accepts a registered name (validated), the string ``"precomputed"``
    (the caller is responsible for routing data through ``attach_index``),
    or a raw ``[m,d]x[r,d]->[m,r]`` callable — auto-registered under a name
    derived from the function (idempotent for the same object, so jit
    caches keyed on the name stay warm).

    Each DISTINCT callable gets its own registry entry for process
    lifetime: re-registering an existing name would silently serve stale
    jit traces keyed on that name.  Long-running processes that generate
    many throwaway lambdas should ``register_metric`` one stable name
    instead.
    """
    if isinstance(metric, str):
        get_metric(metric)  # raises KeyError for unknown names
        return metric
    if callable(metric):
        for name, fn in _REGISTRY.items():
            if fn is metric:
                return name
        base = getattr(metric, "__name__", None) or "metric"
        name, i = base, 0
        while name in _REGISTRY:   # never clobber an existing registration
            i += 1
            name = f"{base}_{i}"
        register_metric(name, metric)
        return name
    raise TypeError(f"metric must be a registered name, 'precomputed', or a "
                    f"callable; got {type(metric).__name__}")


register_metric("l2", l2)
register_metric("l2sq", l2sq)
register_metric("l1", l1)
register_metric("cosine", cosine)
register_metric("precomputed", precomputed)


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise(x: jnp.ndarray, y: jnp.ndarray, *, metric: str = "l2") -> jnp.ndarray:
    """Jitted pairwise dissimilarity ``[m, d] x [r, d] -> [m, r]``."""
    return get_metric(metric)(x, y)
