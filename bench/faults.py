"""Faults planted in the timed path, each of which ``correct`` must catch.

Each planter takes a ``patch(obj, name, value)`` (``monkeypatch.setattr`` in
the tests, :func:`planted` on the chip) and breaks the program underneath the
harness, where the answer is produced.  The harness then runs as it always
does; the comparison with the reference has to read ``correct`` false.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# write path
# ---------------------------------------------------------------------------


def state_unchanged_write(patch):
    """A push that leaves the detector's carried snapshot and embedding as
    they were: every later transition is scored against a stale one."""
    from repro.core.sequence import SequenceDetector

    orig = SequenceDetector.push

    def push(self, a):
        prev = self._prev
        out = orig(self, a)
        if prev is not None:
            self._prev = prev
        return out

    patch(SequenceDetector, "push", push)


def half_batch_write(patch):
    """Scoring sums over every other column only, doubled: half the batch
    left out, the mean taken over the rest."""
    import repro.core.cad as cad

    def half(tile, b1, b2, z1, z2, v1, v2):
        def dist(z, vol):
            zi, zj = z[tile.rows], z[tile.cols]
            return vol * (
                jnp.sum(zi * zi, -1)[:, None] + jnp.sum(zj * zj, -1)[None, :] - 2.0 * zi @ zj.T
            )

        de = jnp.abs(b1 - b2) * jnp.abs(dist(z1, v1) - dist(z2, v2))
        keep = (tile.cols % 2 == 0)[None, :]
        return 2.0 * jnp.sum(jnp.where(keep, de, 0.0), axis=1)

    patch(cad, "_cad_scores_body", half)


def answer_altered_write(patch):
    """One node's score raised by half the largest, where scores are made."""
    import repro.core.sequence as seq

    orig = seq.node_anomaly_scores

    def altered(*args, **kwargs):
        s = orig(*args, **kwargs)
        return s.at[1].add(0.5 * jnp.max(s))

    patch(seq, "node_anomaly_scores", altered)


def exchange_left_out(patch):
    """Cannon's shifts between chips replaced by the identity."""
    import repro.core.distmatrix as dm

    orig = dm._cannon_perms

    def no_shift(R, C):
        skew_a, skew_b, shift_a, shift_b = orig(R, C)
        ident = [(i, i) for i in range(R * C)]
        return skew_a, skew_b, ident, ident

    patch(dm, "_cannon_perms", no_shift)


WRITE = {
    "state_unchanged": state_unchanged_write,
    "half_batch": half_batch_write,
    "answer_altered": answer_altered_write,
}
SHARDED = {"exchange_left_out": exchange_left_out}

# ---------------------------------------------------------------------------
# read path
# ---------------------------------------------------------------------------


def state_unchanged_read(patch):
    """The query kernel returns its running top-k state unchanged."""
    import repro.kernels.emb_query as eq

    patch(eq, "panel_topk_update", lambda vals, idx, *a, **k: (vals, idx))


def half_batch_read(patch):
    """Every other Z panel skipped by the query walk."""
    import repro.kernels.emb_query as eq

    orig = eq.panel_topk_update

    def half(vals, idx, zq, zp, idq, idp, vol, row0, ex, **kw):
        if (int(row0) // zp.shape[0]) % 2:
            return vals, idx
        return orig(vals, idx, zq, zp, idq, idp, vol, row0, ex, **kw)

    patch(eq, "panel_topk_update", half)


def answer_altered_read(patch):
    """A nearest-neighbour answer's first id moved to the next node."""
    import repro.core.query as query

    orig = query.nearest_neighbors

    def altered(*args, **kwargs):
        res = orig(*args, **kwargs)
        res.idx = res.idx.copy()
        res.idx[0] = (res.idx[0] + 1) % args[0].shape[0]
        return res

    patch(query, "nearest_neighbors", altered)


READ = {
    "state_unchanged": state_unchanged_read,
    "half_batch": half_batch_read,
    "answer_altered": answer_altered_read,
}


def for_cell(kind: str, chips: int) -> dict:
    """The faults a cell of this loop kind and chip count can have."""
    if kind == "read":
        return dict(READ)
    return {**WRITE, **(SHARDED if chips > 1 else {})}


@contextlib.contextmanager
def planted(planter):
    """Plant one fault for the block, then put the program back."""
    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    planter(patch)
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)
