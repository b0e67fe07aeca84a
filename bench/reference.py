"""The plain reference that decides ``correct``, and its control.

Nothing here imports the program.  Distances are formed from explicit
differences (no matmul, so no matmul precision enters them), in row
blocks so that a reference of any size fits beside nothing else on the
chip; losses are summed in float64 on the host.  ``dense_distances`` is
a copy of the reference in ``chip_smoke.py`` (at the commit that added
this benchmark).

The numbers compared, each against a limit of its cell:

* ``loss_gap``  — |reported loss − reference loss of the reported
  medoids| / reference loss.
* ``label_gap`` — the widest gap by which a returned label's reference
  distance lies above the row's nearest medoid, over the mean
  nearest-medoid distance (a medoid's own row reads 0).
* ``swap_gain`` — the most that any one swap of a medoid for another
  point would lower the loss, over the loss (PAM's SWAP step, exactly).
  An exact-PAM answer is a swap optimum and reads 0 up to rounding; so
  does BanditPAM's, which returns PAM's medoids with high probability.

The control (``control_distances``) is this reference computed one
precision step below what the configuration states: for l2, the norm
expansion with its cross term at ``Precision.HIGH`` (three bf16 passes
on a TPU; where the platform runs ``HIGH`` as full float32, as the CPU
does, the three passes ``hi·hi + hi·lo + lo·hi`` written out); for l1,
bfloat16 operands in place of float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _block(metric: str):
    def block(xc, med):
        diff = xc[:, None, :] - med[None, :, :]
        if metric == "l1":
            return jnp.sum(jnp.abs(diff), axis=-1)
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    return block


def _row_blocks(x: np.ndarray, rows: int) -> np.ndarray:
    n = x.shape[0]
    xp = np.zeros((-(-n // rows) * rows, x.shape[1]), np.float32)
    xp[:n] = x
    return xp.reshape(-1, rows, x.shape[1])


def dense_distances(x: np.ndarray, med: np.ndarray, metric: str,
                    rows: int = 2048) -> np.ndarray:
    """``[n, d] x [k, d] -> [n, k]`` from explicit differences."""
    block = _block(metric)
    out = jax.jit(lambda a, m: jax.lax.map(lambda xc: block(xc, m), a))(
        jnp.asarray(_row_blocks(x, rows)), jnp.asarray(med))
    return np.asarray(out).reshape(-1, med.shape[0])[:x.shape[0]]


def control_distances(x: np.ndarray, med: np.ndarray, metric: str,
                      rows: int = 2048) -> np.ndarray:
    """The reference one precision step down (see the module doc)."""
    if jax.default_backend() == "tpu":
        def cross(a, b):
            return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGH)
    else:
        cross = _three_pass_dot
    if metric == "l1":
        def block(xc, m):
            diff = (xc.astype(jnp.bfloat16)[:, None, :]
                    - m.astype(jnp.bfloat16)[None, :, :])
            return jnp.sum(jnp.abs(diff).astype(jnp.float32), axis=-1)
    else:
        def block(xc, m):
            sq = (jnp.sum(xc * xc, -1)[:, None] + jnp.sum(m * m, -1)[None]
                  - 2.0 * cross(xc, m))
            return jnp.sqrt(jnp.maximum(sq, 0.0))
    out = jax.jit(lambda a, m: jax.lax.map(lambda xc: block(xc, m), a))(
        jnp.asarray(_row_blocks(x, rows)), jnp.asarray(med))
    return np.asarray(out).reshape(-1, med.shape[0])[:x.shape[0]]


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three_pass_dot(a, b):
    """``a @ b.T`` as three bf16 passes; each pass multiplies bf16 values
    exactly (their products fit in float32) and sums in float32."""
    ah, al = _split(a)
    bh, bl = _split(b)

    def mm(x, y):
        return jnp.matmul(x, y.T, precision=HIGHEST)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def loss_of(dist: np.ndarray) -> float:
    return float(np.sum(dist.min(axis=1).astype(np.float64)))


def loss_gap(loss: float, ref_loss: float) -> float:
    return abs(float(loss) - ref_loss) / ref_loss


def label_gap(ref: np.ndarray, labels: np.ndarray, scale: float) -> float:
    """Widest ``(ref[i, labels[i]] - min_j ref[i, j]) / scale``; a label
    outside ``[0, k)`` reads infinite."""
    labels = np.asarray(labels).astype(np.int64)
    if labels.shape[0] != ref.shape[0]:
        return float("inf")
    if labels.size == 0:
        return 0.0
    if labels.min() < 0 or labels.max() >= ref.shape[1]:
        return float("inf")
    got = ref[np.arange(ref.shape[0]), labels].astype(np.float64)
    best = ref.min(axis=1).astype(np.float64)
    return float(np.max((got - best) / scale))


def _pair(metric: str):
    """Distances of a block of rows to all rows: l1 from explicit
    differences; l2 through the norm expansion at ``highest`` precision,
    whose rounding (~1e-7 of a distance) lies far below the gains this
    is read for."""
    def pair(xa, xb):
        if metric == "l1":
            return jnp.sum(jnp.abs(xa[:, None, :] - xb[None, :, :]), axis=-1)
        sq = (jnp.sum(xa * xa, -1)[:, None] + jnp.sum(xb * xb, -1)[None]
              - 2.0 * jnp.matmul(xa, xb.T, precision=HIGHEST))
        return jnp.sqrt(jnp.maximum(sq, 0.0))
    return pair


def _swap_deltas(metric: str):
    """For every candidate row x and medoid slot j, the change of the
    loss if medoid j were replaced by x (FastPAM1's decomposition of
    PAM's SWAP step): a point i whose nearest medoid is j moves to
    ``min(d(i, x), second_i)``, any other point to ``min(d(i, x),
    nearest_i)``.  Returns the most negative change per candidate."""
    pair = _pair(metric)

    def best(blocks, x, near, second, onehot):
        def step(_, xb):
            d = pair(xb, x)                                  # [rows, n]
            shared = jnp.minimum(d - near[None], 0.0)
            own = jnp.minimum(d, second[None]) - near[None] - shared
            delta = (jnp.sum(shared, axis=1)[:, None]
                     + jnp.matmul(own, onehot, precision=HIGHEST))
            return None, jnp.min(delta, axis=1)
        _, out = jax.lax.scan(step, None, blocks)
        return out.reshape(-1)
    return jax.jit(best)


def swap_gain(x: np.ndarray, medoids: np.ndarray, ref: np.ndarray,
              metric: str) -> float:
    """The largest loss decrease any single swap of a medoid for another
    point would give, over the loss: 0 at a PAM swap optimum.  ``ref``
    holds the reference distances to the medoids, in their order.  A
    medoid index out of range or repeated reads infinite."""
    medoids = np.asarray(medoids).astype(np.int64)
    n, k = x.shape[0], ref.shape[1]
    if (medoids.shape[0] != k or medoids.min() < 0 or medoids.max() >= n
            or len(set(medoids.tolist())) != k):
        return float("inf")
    rows = 8 if metric == "l1" else 512
    order = np.sort(ref, axis=1)
    second = order[:, 1] if k > 1 else np.full(n, np.inf, np.float32)
    onehot = np.eye(k, dtype=np.float32)[ref.argmin(axis=1)]
    delta = np.asarray(_swap_deltas(metric)(
        jnp.asarray(_row_blocks(x, rows)), jnp.asarray(x),
        jnp.asarray(order[:, 0]), jnp.asarray(second),
        jnp.asarray(onehot)))[:n].astype(np.float64)
    return max(0.0, -float(delta.min())) / loss_of(ref)
