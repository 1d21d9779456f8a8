"""The one traffic generator: turns a traffic file's parameters into inputs.

A traffic file (``bench/traffic/<name>.json``) names its ``kind`` and its
``loop``; everything else in it is a parameter read here.  Kinds:

* ``climate_fields`` (write loop): per-location monthly profiles on the
  configuration's lat x lon grid, smooth random fields that drift month to
  month, with a localized event region switched on for one snapshot in every
  ``event.period``.  The program's climate kernel turns features into the
  adjacency.
* ``gmm_points`` (write loop): points of a 2-D Gaussian mixture that drift
  each step, plus uniform edges injected with probability ``inject_p`` per
  node pair at every step after the first.  The injection is made on the
  device from a counter hash of (seed, t, min(i, j), max(i, j)): symmetric by
  construction, zero on the diagonal, born sharded.
* ``embedding_queries`` (read loop): a committed embedding artifact made from
  the seed, and an open-loop schedule of queries at a fixed rate.

Everything is drawn from ``--seed``: the same seed gives the same inputs.
Seeds are whole numbers of any size; :func:`seed_words` folds them into
32-bit words.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> tuple[int, int]:
    """The low and high 32-bit words of a seed of any size."""
    seed = int(seed)
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def seed_key(seed: int, *stream: int) -> jax.Array:
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    for s in stream:
        key = jax.random.fold_in(key, s)
    return key


def host_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([*seed_words(seed), *stream])


# ---------------------------------------------------------------------------
# climate_fields
# ---------------------------------------------------------------------------


def _smooth(f: jax.Array, passes: int) -> jax.Array:
    """Diffuse a (lat, lon, channels) field: the generator's smoothing step."""
    for _ in range(passes):
        f = 0.5 * f + 0.125 * (
            jnp.roll(f, 1, 0) + jnp.roll(f, -1, 0) + jnp.roll(f, 1, 1) + jnp.roll(f, -1, 1)
        )
    return f


@partial(jax.jit, static_argnames=("shape", "passes", "scale"))
def _smooth_noise(key, shape, passes, scale):
    return _smooth(scale * jax.random.normal(key, shape, jnp.float32), passes)


class ClimateSnapshots:
    """Snapshot t's node features, drawn in order from the seed."""

    kind = "climate_fields"

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.lat, self.lon = int(config["n_lat"]), int(config["n_lon"])
        self.n = self.lat * self.lon
        self.channels = int(traffic["channels"])
        self.passes = int(traffic["smooth_passes"])
        self.drift = float(traffic["drift"])
        self.sigma = float(traffic["sigma"])
        ev = traffic["event"]
        self.period, self.phase = int(ev["period"]), int(ev["phase"])
        self.seed = seed
        shape = (self.lat, self.lon, self.channels)
        self._shape = shape
        rng = host_rng(seed, 1)
        n_event = max(1, int(float(ev["frac"]) * self.n))
        ci, cj = int(rng.integers(0, self.lat)), int(rng.integers(0, self.lon))
        ii, jj = np.meshgrid(np.arange(self.lat), np.arange(self.lon), indexing="ij")
        dist = ((ii - ci) ** 2 + (jj - cj) ** 2).reshape(-1)
        self.event_nodes = np.sort(np.argsort(dist, kind="stable")[:n_event])
        bump = np.zeros((self.n, self.channels), np.float32)
        bump[self.event_nodes] = float(ev["strength"])
        self._bump = _smooth(jnp.asarray(bump.reshape(shape)), int(ev["smooth_passes"]))
        self._t = -1
        self._field = None

    def event_on(self, t: int) -> bool:
        return t % self.period == self.phase

    def truth(self, t: int) -> np.ndarray:
        """The event nodes when transition (t-1, t) switches the event."""
        if self.event_on(t - 1) != self.event_on(t):
            return self.event_nodes
        return np.empty(0, np.int64)

    def features(self, t: int) -> jax.Array:
        """(n, channels) float32 features of snapshot t (steps in order)."""
        if t < self._t or self._field is None:
            self._t = 0
            self._field = _smooth_noise(seed_key(self.seed, 2, 0), self._shape, self.passes, 1.0)
        while self._t < t:
            self._t += 1
            step = _smooth_noise(
                seed_key(self.seed, 2, self._t), self._shape, self.passes, self.drift
            )
            self._field = self._field + step
        f = self._field + (self._bump if self.event_on(t) else 0.0)
        return f.reshape(self.n, self.channels)

    def graph_params(self) -> tuple:
        """What the reference needs to rebuild the adjacency from features."""
        return (("sigma", self.sigma),)

    def adjacency(self, ctx, t: int) -> jax.Array:
        """Snapshot t's adjacency, built by the program's climate kernel."""
        from repro.graphs import gaussian_kernel_graph

        return gaussian_kernel_graph(ctx, self.features(t), sigma=self.sigma)


# ---------------------------------------------------------------------------
# gmm_points
# ---------------------------------------------------------------------------


def _fmix32(h: jax.Array) -> jax.Array:
    """MurmurHash3's 32-bit finalizer (a bijection on uint32)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def injection_block(words, t, rows, cols, inject_p):
    """Injected edge weights at global (rows, cols): uniform [0, 1) with
    probability ``inject_p`` per unordered node pair, 0 on the diagonal.
    ``words`` are the seed's two 32-bit words; all integer operands uint32."""
    lo, hi = jnp.minimum(rows, cols), jnp.maximum(rows, cols)
    h = _fmix32(words[0] ^ jnp.uint32(0x3C6EF372))
    h = _fmix32(h ^ words[1])
    h = _fmix32(h ^ t)
    h = _fmix32(h ^ lo)
    h = _fmix32(h ^ (hi * jnp.uint32(0x9E3779B1)))
    u_mask = (h >> 8).astype(jnp.float32) * jnp.float32(2.0**-24)
    h2 = _fmix32(h ^ jnp.uint32(0xA511E9B3))
    u_val = (h2 >> 8).astype(jnp.float32) * jnp.float32(2.0**-24)
    keep = (u_mask < inject_p) & (rows != cols)
    return jnp.where(keep, u_val, jnp.float32(0.0))


@partial(jax.jit, static_argnames=("n", "sharding"))
def _injection(words, t, inject_p, n, sharding):
    rows = jax.lax.broadcasted_iota(jnp.uint32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (n, n), 1)
    r = injection_block(words, t, rows, cols, inject_p)
    return jax.lax.with_sharding_constraint(r, sharding)


@partial(jax.jit, static_argnames=("n", "components"))
def _gmm_start(key, n, components, spread):
    signs = jnp.array([[1, 1], [1, -1], [-1, 1], [-1, -1]], jnp.float32)[:components]
    k_comp, k_pts = jax.random.split(key)
    comp = jax.random.randint(k_comp, (n,), 0, components)
    return spread * signs[comp] + jax.random.normal(k_pts, (n, 2), jnp.float32)


@partial(jax.jit, static_argnames=("n",))
def _gmm_step(key, pts, n, drift):
    return pts + drift * jax.random.normal(key, (n, 2), jnp.float32)


class GMMSnapshots:
    """Snapshot t's points and injected edges, drawn in order from the seed."""

    kind = "gmm_points"

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.n = int(config["n"])
        self.components = int(traffic["components"])
        self.spread = float(traffic["spread"])
        self.drift = float(traffic["drift"])
        self.inject_p = float(traffic["inject_p"])
        self.bandwidth = float(traffic["bandwidth"])
        self.seed = seed
        self.words = jnp.asarray(np.array(seed_words(seed), np.uint32))
        self._t = -1
        self._pts = None

    def features(self, t: int) -> jax.Array:
        """(n, 2) float32 points of snapshot t (steps in order)."""
        if t < self._t or self._pts is None:
            self._t = 0
            self._pts = _gmm_start(
                seed_key(self.seed, 3, 0), self.n, self.components, self.spread
            )
        while self._t < t:
            self._t += 1
            self._pts = _gmm_step(seed_key(self.seed, 3, self._t), self._pts, self.n, self.drift)
        return self._pts

    def injected(self, t: int) -> bool:
        return t >= 1

    def injection(self, t: int, sharding) -> jax.Array:
        return _injection(
            self.words, jnp.uint32(t), jnp.float32(self.inject_p), self.n, sharding
        )

    def graph_params(self) -> tuple:
        return (("bandwidth", self.bandwidth), ("inject", True), ("inject_p", self.inject_p))

    def adjacency(self, ctx, t: int) -> jax.Array:
        """The program's similarity graph of snapshot t's points, plus the
        injected edges of step t."""
        from repro.graphs import similarity_graph

        a = similarity_graph(ctx, self.features(t), bandwidth=self.bandwidth)
        if self.injected(t):
            a = a + self.injection(t, ctx.sharding(ctx.matrix_spec))
        return a


SNAPSHOT_KINDS = {"climate_fields": ClimateSnapshots, "gmm_points": GMMSnapshots}


def snapshots(traffic: dict, config: dict, seed: int):
    return SNAPSHOT_KINDS[traffic["kind"]](traffic, config, seed)


# ---------------------------------------------------------------------------
# embedding_queries
# ---------------------------------------------------------------------------


def embedding_artifact(traffic: dict, n: int, k: int, seed: int):
    """A committed embedding made from the seed: ``(z, vol, deg)``.

    ``z`` (n, k) float32 with unit-normal entries scaled by ``z_scale``;
    ``deg`` uniform on ``deg_range`` times n (a dense graph's degrees grow
    with n); ``vol`` their sum.
    """
    art = traffic["artifact"]
    rng = host_rng(seed, 4)
    z = (float(art["z_scale"]) * rng.standard_normal((n, k))).astype(np.float32)
    lo, hi = art["deg_range"]
    deg = (n * rng.uniform(lo, hi, n)).astype(np.float32)
    return z, float(deg.astype(np.float64).sum()), deg


@dataclass(frozen=True)
class Query:
    due_s: float  # offset from the window's start at which it is due
    kind: str  # "nearest_neighbors" | "top_anomalies"
    node: int  # the query node (nearest_neighbors), -1 otherwise
    k: int


def zipf_ranks(rng: np.random.Generator, n: int, s: float, size: int) -> np.ndarray:
    """``size`` ranks in [0, n) with P(rank r) proportional to 1 / (r+1)^s."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"), n - 1)


def poisson_gaps(rng: np.random.Generator, rate: float, count: int) -> np.ndarray:
    """``count`` exponential inter-arrival gaps of mean 1/rate: the same set
    of gaps (the distribution's quantiles) for every seed, in a seeded
    order, so seeds vary the order of the arrivals and not the load."""
    q = (np.arange(count) + 0.5) / count
    return rng.permutation(-np.log1p(-q) / rate)


def query_schedule(traffic: dict, n: int, seed: int, seconds: float) -> list[Query]:
    """The open-loop schedule of one window: ``rate_per_s * seconds``
    queries due at Poisson arrival times, the mix's kinds in their exact
    shares in a seeded order, nearest-neighbour nodes Zipf-distributed over a
    seeded permutation of the n nodes.

    The arrival times come from the traffic's own ``arrival_seed``, not the
    run's: near capacity the order of the gaps moves the tail latency more
    than the system does, so every run offers the same load at the same
    times and the seed picks what is asked."""
    rng = host_rng(seed, 5)
    count = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    gaps = poisson_gaps(
        np.random.default_rng(int(traffic["arrival_seed"])), float(traffic["rate_per_s"]), count
    )
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    kinds: list[dict] = []
    for entry in traffic["mix"]:
        kinds += [entry] * int(round(float(entry["share"]) * count))
    kinds = (kinds + [traffic["mix"][0]] * count)[:count]
    kinds = [kinds[j] for j in rng.permutation(count)]
    perm = rng.permutation(n)
    nodes = np.full(count, -1, np.int64)
    for entry in traffic["mix"]:
        at = [i for i, e in enumerate(kinds) if e is entry and e["query"] == "nearest_neighbors"]
        if at:
            nodes[at] = perm[zipf_ranks(rng, n, float(entry["zipf_s"]), len(at))]
    return [
        Query(float(due[i]), e["query"], int(nodes[i]), int(e["k"]))
        for i, e in enumerate(kinds)
    ]
