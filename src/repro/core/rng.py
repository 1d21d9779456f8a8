"""Counter-based (stateless) RNG for edge-space random projection.

The Spielman-Srivastava projection needs a Rademacher vector q of length
m = n^2 (one entry per edge).  Materializing q is exactly the
"larger-than-memory" trap the paper avoids with Spark streaming; the TPU-native
equivalent is to *never store q at all*: every entry is a pure integer hash of
(seed, i, j, projection_column), so any device can (re)generate any tile of the
edge randomness on the fly, bit-exactly, with no communication and no storage.

The hash is a splitmix32-style finalizer over uint32 lanes.  It is written in
plain jnp ops so the identical code runs inside a Pallas kernel body, in the
pure-jnp oracle, and under vmap/jit -- the kernel and the reference are
bit-identical by construction.

Antisymmetry convention: the incidence matrix orients every edge {i, j} (i<j)
from head i to tail j, so q contributes +q_e to row i and -q_e to row j.  We
encode this as an antisymmetric matrix Q with Q[i, j] = -Q[j, i] and
Q[i, i] = 0, generated from the canonical (min, max) pair.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars (not jnp arrays): they fold into jaxprs as literals, so the
# hash can run inside Pallas kernel bodies without captured-constant errors.
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
_GOLD = np.uint32(0x9E3779B9)


def splitmix32(h: jax.Array) -> jax.Array:
    """splitmix32 finalizer; uniform uint32 -> uint32 bijection."""
    with np.errstate(over="ignore"):  # uint32 wraparound is the point
        h = jnp.asarray(h).astype(jnp.uint32) if not isinstance(h, np.uint32) else h
        h = (h ^ (h >> np.uint32(16))) * _M1
        h = (h ^ (h >> np.uint32(15))) * _M2
        return h ^ (h >> np.uint32(16))


def _u32(x) -> jax.Array | np.uint32:
    """Python ints fold to numpy literals (Pallas-safe); arrays are cast."""
    if isinstance(x, (int, np.integer)):
        return np.uint32(x & 0xFFFFFFFF)
    return jnp.asarray(x).astype(jnp.uint32)


def hash_u32(*parts: jax.Array) -> jax.Array:
    """Combine integer streams into one uniform uint32 stream."""
    h = np.uint32(0x243F6A88)  # pi fractional bits
    with np.errstate(over="ignore"):  # uint32 wraparound is the point
        for p in parts:
            h = splitmix32(h ^ (_u32(p) * _GOLD + _GOLD))
    return h


def edge_rademacher(
    seed: jax.Array | int,
    rows: jax.Array,
    cols: jax.Array,
    col_id: jax.Array | int,
) -> jax.Array:
    """Antisymmetric Rademacher field Q[i, j] in {-1, 0, +1} (0 on diagonal).

    ``rows``/``cols`` are (broadcastable) global index arrays; ``col_id`` is the
    projection-column counter.  Q[i, j] = -Q[j, i]; entries for i<j are iid
    +/-1 with p=1/2, keyed on (seed, min, max, col_id).
    """
    rows = jnp.asarray(rows)
    cols = jnp.asarray(cols)
    lo = jnp.minimum(rows, cols)
    hi = jnp.maximum(rows, cols)
    h = hash_u32(_u32(seed), lo, hi, _u32(col_id))
    # +/-1 from the top bit (via int32: Mosaic has no uint32 -> f32 cast)
    base = 1.0 - 2.0 * (h >> 31).astype(jnp.int32).astype(jnp.float32)
    orient = jnp.where(rows < cols, 1.0, -1.0).astype(jnp.float32)
    return jnp.where(rows == cols, 0.0, base * orient)


def uniform01(seed: jax.Array | int, *parts: jax.Array) -> jax.Array:
    """Uniform float32 in [0, 1) keyed on integer counters."""
    h = hash_u32(jnp.asarray(seed, jnp.uint32), *parts)
    return h.astype(jnp.float32) * jnp.float32(2.0**-32)
