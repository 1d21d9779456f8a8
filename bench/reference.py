"""The plain reference of CADDeLaG: the same semantics, none of the program.

Written from the paper's algorithms in straightforward ``jax.numpy`` on
float32, imported by nothing of the program and taking nothing it made:

* the adjacency from the traffic's node features (the climate kernel
  ``exp(-|x_i - x_j|^2 / 2 sigma^2)``, the GMM similarity
  ``exp(-|x_i - x_j| / bandwidth)`` plus the injected edges), zero diagonal;
* the inverse chain (Algorithm 2): ``S~ = D^-1/2 A D^-1/2 - u u^T`` with
  ``u = sqrt(d / vol)``, ``T <- T T``, ``P <- P T + P`` over ``d - 1``
  levels from ``P = I + S~``; ``P1 = D^-1/2 P D^-1/2``; ``P2 = P1 (D - A)``;
* the edge-space projection (Algorithm 3): ``Y[i, c] = sum_j sqrt(A_ij)
  Q_c[i, j] / sqrt(k)``, where ``Q_c`` is the configuration's antisymmetric
  Rademacher field.  The field is part of the configuration (its seed picks
  the random projection), so it is regenerated here from its definition: the
  sign bit of a splitmix32 chain over (seed, min(i, j), max(i, j), c), +1 for
  i < j, -1 for i > j, 0 on the diagonal;
* the solve: ``chi = P1 Y`` and ``q - 1`` Richardson steps
  ``z <- z - P2 z + chi``, each column kept at mean zero;
* the scores (Algorithm 4): ``F_i = sum_j |A1_ij - A2_ij|
  |vol1 |z1_i - z1_j|^2 - vol2 |z2_i - z2_j|^2|``.

``passes`` sets the precision of every matrix product: 6 is float32 at
``HIGHEST`` (what the configuration states), 3 is the three-pass bfloat16
split that ``Precision.HIGH`` makes (the control).  The three-pass product is
written out, so it means the same on any device.

Row-wise quantities of the read path (commute distances from a persisted
embedding) are computed on the host in float64 (:func:`query_answer`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import traffic as tf

_HIGHEST = lax.Precision.HIGHEST


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def mm(a, b, passes: int):
    """``a @ b`` in float32 at the given number of bfloat16 passes."""
    if passes == 6:
        return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=jnp.float32)
    if passes != 3:
        raise ValueError(f"passes must be 6 or 3, got {passes}")
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    dot = partial(jnp.dot, precision=_HIGHEST, preferred_element_type=jnp.float32)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


# ---------------------------------------------------------------------------
# the configuration's random projection, from its definition
# ---------------------------------------------------------------------------


def _splitmix32(h):
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def rademacher(seed_u32, rows, cols, c):
    """Q_c[rows, cols] in {-1, 0, +1}, antisymmetric, 0 on the diagonal."""
    gold = jnp.uint32(0x9E3779B9)
    lo, hi = jnp.minimum(rows, cols), jnp.maximum(rows, cols)
    h = jnp.uint32(0x243F6A88)
    for part in (seed_u32, lo, hi, c):
        h = _splitmix32(h ^ (part.astype(jnp.uint32) * gold + gold))
    sign = 1.0 - 2.0 * (h >> 31).astype(jnp.float32)
    orient = jnp.where(rows < cols, 1.0, -1.0)
    return jnp.where(rows == cols, 0.0, sign * orient)


# ---------------------------------------------------------------------------
# adjacency
# ---------------------------------------------------------------------------


def _sq_dist(xi, xj):
    d2 = jnp.zeros((xi.shape[0], xj.shape[0]), jnp.float32)
    for c in range(xi.shape[1]):
        d2 = d2 + (xi[:, c][:, None] - xj[:, c][None, :]) ** 2
    return d2


def adjacency(kind: str, params: dict, feats, t, words, n: int):
    """The (n, n) adjacency of one snapshot from its features."""
    rows = lax.broadcasted_iota(jnp.uint32, (n, n), 0)
    cols = lax.broadcasted_iota(jnp.uint32, (n, n), 1)
    d2 = _sq_dist(feats, feats)
    if kind == "climate_fields":
        a = jnp.exp(-d2 / (2.0 * params["sigma"] ** 2))
    elif kind == "gmm_points":
        a = jnp.exp(-jnp.sqrt(jnp.maximum(d2, 1e-12)) / params["bandwidth"])
    else:
        raise ValueError(f"no reference adjacency for traffic kind {kind!r}")
    a = jnp.where(rows == cols, 0.0, a)
    if params.get("inject"):
        a = a + jnp.where(
            t >= 1, tf.injection_block(words, t, rows, cols, params["inject_p"]), 0.0
        )
    return a


# ---------------------------------------------------------------------------
# embedding and scores
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("kind", "pkey", "n", "k", "d", "steps", "passes", "sharding"))
def embedding(feats, t, words, proj_seed, *, kind, pkey, n, k, d, steps, passes, sharding):
    """``(z, vol)`` of one snapshot: Algorithms 2 and 3, then the solve."""
    params = dict(pkey)
    keep = partial(lax.with_sharding_constraint, shardings=sharding)
    a = keep(adjacency(kind, params, feats, t, words, n))
    deg = jnp.sum(a, axis=1)
    vol = jnp.sum(deg)
    inv = jnp.where(deg > 0, lax.rsqrt(jnp.maximum(deg, 1e-30)), 0.0)
    u = jnp.sqrt(deg / vol)
    s = keep(a * inv[:, None] * inv[None, :] - u[:, None] * u[None, :])
    rows = lax.broadcasted_iota(jnp.uint32, (n, n), 0)
    cols = lax.broadcasted_iota(jnp.uint32, (n, n), 1)
    eye = (rows == cols).astype(jnp.float32)
    t_mat, p_mat = s, keep(s + eye)
    for _ in range(d - 1):
        t_mat = keep(mm(t_mat, t_mat, passes))
        p_mat = keep(mm(p_mat, t_mat, passes) + p_mat)
    p1 = keep(p_mat * inv[:, None] * inv[None, :])
    p2 = keep(mm(p1, keep(eye * deg[:, None] - a), passes))
    root = jnp.sqrt(jnp.maximum(a, 0.0))
    y = jnp.stack(
        [jnp.sum(root * rademacher(proj_seed, rows, cols, jnp.uint32(c)), axis=1) for c in range(k)],
        axis=1,
    ) / jnp.sqrt(jnp.float32(k))
    chi = mm(p1, y, passes)
    chi = chi - jnp.mean(chi, axis=0, keepdims=True)
    z = chi
    for _ in range(steps):
        z = z - mm(p2, z, passes) + chi
        z = z - jnp.mean(z, axis=0, keepdims=True)
    return z, vol


def _commute(z, vol, passes):
    sq = jnp.sum(z * z, axis=1)
    return vol * (sq[:, None] + sq[None, :] - 2.0 * mm(z, z.T, passes))


@partial(jax.jit, static_argnames=("kind", "pkey", "n", "passes", "sharding"))
def scores(f1, f2, t2, words, z1, v1, z2, v2, *, kind, pkey, n, passes, sharding):
    """Node anomaly scores of the transition (t2 - 1, t2)."""
    params = dict(pkey)
    keep = partial(lax.with_sharding_constraint, shardings=sharding)
    a1 = keep(adjacency(kind, params, f1, t2 - 1, words, n))
    a2 = keep(adjacency(kind, params, f2, t2, words, n))
    de = jnp.abs(a1 - a2) * jnp.abs(keep(_commute(z1, v1, passes)) - keep(_commute(z2, v2, passes)))
    return jnp.sum(de, axis=1)


def transition_scores(snaps, t2: int, cfg, sharding, *, passes: int = 6) -> np.ndarray:
    """Reference scores of transition (t2 - 1, t2) of a write cell's
    traffic (``snaps`` from :func:`bench.traffic.snapshots`); ``cfg`` is the
    configuration file's contents."""
    words = getattr(snaps, "words", jnp.zeros((2,), jnp.uint32))
    kind, pkey = snaps.kind, snaps.graph_params()
    n = snaps.n
    k = k_rp(n, float(cfg["eps_rp"]))
    proj_seed = jnp.uint32(projection_seed(snaps.seed))
    out = []
    with jax.default_matmul_precision("highest"):
        for t in (t2 - 1, t2):
            f = snaps.features(t)
            out.append(embedding(
                f, jnp.uint32(t), words, proj_seed, kind=kind, pkey=pkey, n=n, k=k,
                d=int(cfg["d"]), steps=int(cfg["q"]) - 1, passes=passes, sharding=sharding,
            ))
        (z1, v1), (z2, v2) = out
        f = scores(
            snaps.features(t2 - 1), snaps.features(t2), jnp.uint32(t2), words,
            z1, v1, z2, v2, kind=kind, pkey=pkey, n=n, passes=passes, sharding=sharding,
        )
        return np.asarray(f, np.float64)


def k_rp(n: int, eps_rp: float) -> int:
    """The embedding width: ``ceil(ln(n / eps_RP))`` (paper Sec. 3)."""
    return max(1, int(np.ceil(np.log(n / eps_rp))))


def projection_seed(seed: int) -> int:
    """The configuration seed of a run's random projection, from ``--seed``."""
    return int(seed) & 0x7FFFFFFF


# ---------------------------------------------------------------------------
# read path
# ---------------------------------------------------------------------------


def query_answer(z: np.ndarray, vol: float, kind: str, node: int, k: int, *, passes: int = 0):
    """Reference scores of every node for one query, in float64.

    ``nearest_neighbors``: ``vol |z_j - z_node|^2`` with the node itself set
    to +inf; ``top_anomalies``: ``vol |z_j - zbar|^2`` with ``zbar`` the
    column mean.  ``passes=3`` computes the distance expansion's cross term
    with the three-pass bfloat16 product instead (the control).  Pass ``z``
    as float64 to convert it once for many queries.
    """
    z64 = np.asarray(z, np.float64)
    zq = z64[node] if kind == "nearest_neighbors" else z64.mean(axis=0)
    if passes == 3:
        zf = jnp.asarray(z, jnp.float32)
        q32 = jnp.asarray(zq, jnp.float32)[None, :]
        cross = np.asarray(mm(q32, zf.T, 3), np.float64)[0]
        sq = (z64**2).sum(1)
        d = vol * np.maximum(sq + float((zq**2).sum()) - 2.0 * cross, 0.0)
    else:
        diff = z64 - zq
        d = vol * (diff * diff).sum(axis=1)
    if kind == "nearest_neighbors":
        d[node] = np.inf
    return d
