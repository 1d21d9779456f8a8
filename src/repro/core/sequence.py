"""Sequence engine: amortized CADDeLaG over a stream of T graph snapshots.

The paper's headline object is a *sequence* of dense snapshots (climate
months, election cycles).  Scoring every transition with
:func:`repro.core.cad.detect_anomalies` rebuilds the O(n^3)-GEMM chain
operator for both endpoints -- 2(T-1) builds where T suffice.
:class:`SequenceDetector` computes each snapshot's ``ChainOperator`` /
``Embedding`` exactly once and carries it forward: snapshot t's embedding is
reused as the left endpoint of transition (t, t+1).

Memory follows the paper's "never load the whole sequence" design: only two
snapshots (adjacency + embedding) are resident at any time.  With
``donate=True`` the detector eagerly deletes the outgoing snapshot's device
buffers after its last use (double buffering) -- callers must not touch a
donated snapshot again.

Out-of-core mode: ``push`` (and ``run``) also accept store-backed snapshot
handles (:class:`repro.store.SnapshotHandle`, e.g. from
``TileStore.iter_snapshots()``).  Handles are scored by the streaming tile
executor -- adjacencies stay on host/disk and devices only ever hold two row
*panels* per operand, so residency is bounded by tiles, not snapshots, and n
is bounded by host/disk capacity rather than HBM.

A streaming global top-k across all transitions is maintained by merging each
transition's top-k into the running global top-k over 2k candidates.
"""

from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field, replace
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import chain
from repro.core.cad import CADResult, node_anomaly_scores, top_anomalies
from repro.core.delta_chain import (
    SOLVE_RESIDUAL_SLACK,
    BaseChain,
    build_base_chain,
    try_delta_update,
)
from repro.core.distmatrix import DistContext
from repro.core.embedding import CommuteConfig, Embedding, commute_time_embedding
from repro.obs import phase
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY as _OBS_REGISTRY


@dataclass
class SequenceResult:
    """Per-transition results plus the sequence-wide top-k."""

    transitions: list[CADResult]  # transitions[t] scores snapshot t -> t+1
    global_top_idx: jax.Array  # (k,) node ids
    global_top_val: jax.Array  # (k,) scores
    global_top_step: jax.Array  # (k,) transition index of each entry
    n_snapshots: int
    chain_builds: int  # chain_product invocations during run()
    transition_seconds: list[float] = field(default_factory=list)
    # Registry counter deltas (see repro.obs.metrics) per scored transition,
    # aligned with ``transitions``; ``warmup_metrics`` is the delta of the
    # first push (embedding build only -- nothing scored yet).
    transition_metrics: list[dict] = field(default_factory=list)
    warmup_metrics: dict | None = None


class SequenceDetector:
    """Streaming CADDeLaG over T snapshots with one chain build per snapshot.

    Usage::

        det = SequenceDetector(ctx, cfg, top_k=20)
        for a_t in snapshots:          # iterator; never holds the sequence
            res = det.push(a_t)        # CADResult for (t-1, t), None at t=0
        final = det.finalize()

    or simply ``det.run(snapshots)``.
    """

    def __init__(
        self,
        ctx: DistContext,
        cfg: CommuteConfig | None = None,
        *,
        top_k: int = 10,
        use_kernel: bool = False,
        donate: bool = False,
        emb_store=None,
    ):
        self.ctx = ctx
        self.cfg = cfg or CommuteConfig()
        self.top_k = top_k
        self.use_kernel = use_kernel
        self.donate = donate
        # Write/read split: with an EmbeddingStore attached, every push
        # publishes the committed (z, vol, deg, zbar) artifact so query-path
        # readers (repro.core.query) never touch live solver state.  Duck-
        # typed (put_embedding), so the core keeps zero store imports.
        self.emb_store = emb_store
        self._prev: tuple[jax.Array, Embedding] | None = None
        self._base: BaseChain | None = None  # incremental-chain base (cfg.incremental_chain)
        self._t = 0  # snapshots consumed
        self._transitions: list[CADResult] = []
        self._seconds: list[float] = []
        self._metrics: list[dict] = []
        self._warmup_metrics: dict | None = None
        self._builds0 = chain.chain_build_count()
        self._g_val: jax.Array | None = None
        self._g_idx: jax.Array | None = None
        self._g_step: jax.Array | None = None

    # -- streaming global top-k ---------------------------------------------

    def _merge_topk(self, idx, val, step: int) -> None:
        """Merge one transition's top-k into the running global top-k.

        Ties keep candidate order (``lax.top_k``): the running entries, then
        this transition's in rank order.
        """
        step_arr = jnp.full(idx.shape, step, jnp.int32)
        if self._g_val is not None:
            val = jnp.concatenate([self._g_val, val])
            idx = jnp.concatenate([self._g_idx, idx])
            step_arr = jnp.concatenate([self._g_step, step_arr])
        self._g_val, pos = lax.top_k(val, min(self.top_k, val.shape[0]))
        self._g_idx = idx[pos]
        self._g_step = step_arr[pos]

    # -- snapshot lifecycle --------------------------------------------------

    def _retire_op(self, emb: Embedding) -> Embedding:
        """Drop an embedding's chain operator once its solve is done:
        scoring reads only ``z`` and ``vol``, and publish ``z``, ``vol`` and
        the degrees, so holding P1 / P2 (or a factored operator's d T levels)
        until the snapshot leaves the window would keep those n x n buffers
        alive through the next chain build.  A factored operator's T levels
        are deleted here (``release_scratch``); its adjacency is the
        snapshot's own, which the window keeps for scoring.

        An out-of-core operator's P1 / P2 handles live in a scratch store
        owned by the build; those snapshots are removed here (without this,
        a disk-backed scratch would grow by 2 n^2 bytes per snapshot for the
        whole sequence).  A resident operator is freed by refcount, or
        deleted eagerly under ``donate=True``.  An operator that shares the
        incremental base chain is left alone: ``BaseChain.release()`` owns
        its buffers.
        """
        op = emb.op
        if op is not None:
            op.release_scratch()  # no-op when the op shares the base chain
            if self.donate and not op.shared_base:
                self._delete(op.p1, op.p2)
        return replace(emb, op=None)

    def _release(self, a: jax.Array, emb: Embedding) -> None:
        """Retire an outgoing snapshot as it leaves the two-snapshot window.

        The input snapshot ``a`` may be a store-backed handle -- that is the
        *user's* data and is never removed from its store.  ``donate=True``
        deletes the outgoing *device* buffers eagerly (double buffering);
        callers must not touch a donated snapshot again.
        """
        if self.donate:
            self._delete(a, emb.z)

    @staticmethod
    def _delete(*bufs) -> None:
        for buf in bufs:
            delete = getattr(buf, "delete", None)
            if delete is None:
                continue  # store-backed handle: the user's data, not ours
            try:
                delete()
            except (RuntimeError, ValueError, OSError) as exc:
                # Already-deleted / donated buffers raise here; that is the
                # expected double-buffering race and safe to continue past --
                # but say so, instead of silently eating every exception (a
                # genuinely failing delete used to vanish without a trace).
                warnings.warn(
                    f"snapshot buffer delete failed during release: {exc!r}",
                    RuntimeWarning,
                    stacklevel=3,
                )

    def _incremental_embedding(self, a, warm_from) -> Embedding:
        """Snapshot ``a``'s embedding under incremental mode.

        Tries a low-rank delta update against the retained base chain
        (:func:`repro.core.delta_chain.try_delta_update`); when the drift
        monitor rejects the transition -- or there is no base yet -- the
        accumulated correction collapses into a fresh full build that becomes
        the new base.  The corrected operator is a preconditioner whose
        iteration has the exact solution as its fixed point, so a delta
        transition must solve as far as a rebuild would
        (:meth:`_solved_as_far`), or it falls back to a rebuild too
        (``chain.solve_fallbacks``).  Chain timing lands under the same
        ``phase("chain")`` counter the full-build path uses, so
        per-transition chain seconds stay comparable across modes.
        """
        op = self._incremental_op(a)
        emb = commute_time_embedding(
            self.ctx, a, self.cfg, op=op, use_kernel=self.use_kernel,
            warm_from=warm_from,
        )
        if op.adj is None:  # a rebuild: its residual is the bar deltas meet
            self._base.solved(emb.report.residual)
            return emb
        if self._solved_as_far(emb.report):
            return emb
        del op
        emb = None  # the corrected operator holds base P1: drop it first
        _OBS_REGISTRY.add_named({"chain.solve_fallbacks": 1.0})
        with self._chain_phase(a) as sp:
            self._rebuild(a, sp)
            sp.annotate(fallback="residual")
        emb = commute_time_embedding(
            self.ctx, a, self.cfg, op=self._base.op, use_kernel=self.use_kernel,
            warm_from=warm_from,
        )
        self._base.solved(emb.report.residual)
        return emb

    def _solved_as_far(self, report) -> bool:
        """Whether a delta transition's solve reached what the rebuild
        promises: the configured tolerance where there is one, else within
        ``SOLVE_RESIDUAL_SLACK`` of the base's own fixed-step residual.  A
        NaN on either side is not converged."""
        if self.cfg.solver_tol is not None:
            return report.converged
        return bool(report.residual <= SOLVE_RESIDUAL_SLACK * self._base.residual)

    def _chain_phase(self, a):
        return phase(
            "chain", n=int(a.shape[0]), d=self.cfg.d, oocore=self.cfg.oocore,
            incremental=True,
        )

    def _incremental_op(self, a):
        """The delta-corrected operator for ``a``, or the new base's."""
        with self._chain_phase(a) as sp:
            if self._base is not None:
                op = try_delta_update(self.ctx, self._base, a, self.cfg)
                if op is not None:
                    sp.annotate(mode="delta")
                    return op
            return self._rebuild(a, sp)

    def _rebuild(self, a, sp):
        """Free the old base, then build the new one from ``a`` (its
        operator is returned)."""
        if self._base is not None:
            self._base.release()
            self._base = None
        self._base = build_base_chain(self.ctx, a, self.cfg, use_kernel=self.use_kernel)
        sp.annotate(mode="rebuild")
        op = self._base.op
        sp.fence(op.vol)
        return op

    def _publish(self, emb: Embedding, deg) -> None:
        """Publish snapshot t's committed embedding to the attached store.

        The artifact is a host-side *copy* of (z, vol, deg) -- readers never
        alias live device buffers, so ``donate=True`` double-buffering and
        in-flight solves can't tear a query.  Atomic panel writes +
        commit-on-complete (see :class:`repro.store.embstore.EmbeddingStore`)
        mean a crash mid-publish leaves the previous artifact current.
        """
        with phase("publish", t=self._t, n=int(emb.z.shape[0])):
            self.emb_store.put_embedding(
                f"t{self._t:04d}",
                np.asarray(emb.z),
                float(np.asarray(emb.vol)),
                np.asarray(deg),
            )

    def push(self, a) -> CADResult | None:
        """Consume snapshot t; returns the CADResult for transition (t-1, t).

        ``a`` is a resident sharded adjacency or a store-backed snapshot
        handle (streamed off-core; scores bitwise-identical to the resident
        run with the default chain build, allclose under ``fuse_l=True``).
        Builds exactly one chain operator (for ``a``); the left endpoint's
        operator was built when *it* was pushed.  With
        ``cfg.warm_start=True``, the previous snapshot's solution seeds the
        solver (transition 1 onward) -- a tolerance-targeted solve on a
        slowly-drifting sequence then converges in far fewer iterations.
        """
        t0 = time.perf_counter()
        m0 = _OBS_REGISTRY.snapshot()
        with obs_trace.span("sequence.push", t=self._t) as push_sp:
            warm_from = (
                self._prev[1].z
                if (self.cfg.warm_start and self._prev is not None)
                else None
            )
            if self.cfg.incremental_chain:
                emb = self._incremental_embedding(a, warm_from)
            else:
                emb = commute_time_embedding(
                    self.ctx, a, self.cfg, use_kernel=self.use_kernel,
                    warm_from=warm_from,
                )
            deg = emb.op.deg
            emb = self._retire_op(emb)
            out = None
            if self._prev is not None:
                a_prev, e_prev = self._prev
                # Dispatched before publish: the device scores while the
                # host writes the artifact.
                scores = node_anomaly_scores(
                    self.ctx,
                    a_prev,
                    a,
                    e_prev,
                    emb,
                    use_kernel=self.use_kernel,
                    prefetch_depth=self.cfg.prefetch_depth,
                )
                idx, vals = top_anomalies(scores, self.top_k)
            if self.emb_store is not None:
                self._publish(emb, deg)
            if self._prev is not None:
                out = CADResult(
                    scores=scores, top_idx=idx, top_val=vals,
                    solve_reports=(e_prev.report, emb.report),
                )
                jax.block_until_ready(out.scores)
                self._merge_topk(idx, vals, self._t - 1)
                self._transitions.append(out)
                self._seconds.append(time.perf_counter() - t0)
                self._metrics.append(_OBS_REGISTRY.delta(m0))
                self._release(a_prev, e_prev)
            else:
                self._warmup_metrics = _OBS_REGISTRY.delta(m0)
            push_sp.annotate(scored=out is not None)
        self._prev = (a, emb)
        self._t += 1
        return out

    def finalize(self) -> SequenceResult:
        """Package per-transition results and the sequence-wide top-k.

        A single-snapshot sequence (T=1) has zero transitions by definition
        and finalizes to an empty result; T=0 means the detector never saw a
        snapshot at all, which is a caller bug and raises.
        """
        if self._t == 0:
            raise ValueError(
                "finalize() on an empty sequence: 0 snapshots were pushed "
                "(scoring transitions needs at least 2)"
            )
        if self._base is not None:
            # Retire the incremental base chain: drops the retained T/P level
            # snapshots from the scratch store (and the scratch itself).  The
            # final embedding's z/scores are already materialized; only the
            # operator's scratch handles die here.
            self._base.release()
            self._base = None
        if not self._transitions:  # T == 1: nothing to score, not an error
            return SequenceResult(
                transitions=[],
                global_top_idx=jnp.zeros((0,), jnp.int32),
                global_top_val=jnp.zeros((0,), jnp.float32),
                global_top_step=jnp.zeros((0,), jnp.int32),
                n_snapshots=self._t,
                chain_builds=chain.chain_build_count() - self._builds0,
                transition_seconds=self._seconds,
                transition_metrics=self._metrics,
                warmup_metrics=self._warmup_metrics,
            )
        return SequenceResult(
            transitions=self._transitions,
            global_top_idx=self._g_idx,
            global_top_val=self._g_val,
            global_top_step=self._g_step,
            n_snapshots=self._t,
            chain_builds=chain.chain_build_count() - self._builds0,
            transition_seconds=self._seconds,
            transition_metrics=self._metrics,
            warmup_metrics=self._warmup_metrics,
        )

    def run(self, snapshots: Iterable[jax.Array]) -> SequenceResult:
        """Consume an iterator of T snapshots, score all T-1 transitions."""
        for a in snapshots:
            self.push(a)
        return self.finalize()


def detect_sequence_anomalies(
    ctx: DistContext,
    snapshots: Iterable[jax.Array],
    cfg: CommuteConfig | None = None,
    *,
    top_k: int = 10,
    use_kernel: bool = False,
    donate: bool = False,
) -> SequenceResult:
    """One-shot convenience wrapper around :class:`SequenceDetector`."""
    det = SequenceDetector(ctx, cfg, top_k=top_k, use_kernel=use_kernel, donate=donate)
    return det.run(snapshots)
