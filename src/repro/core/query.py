"""Query-scale read path: serve top-k / nearest-neighbor / pairwise reads
from a persisted embedding artifact.

The write path (chain build + solve) costs O(n^3) GEMM work per snapshot;
once :class:`~repro.store.embstore.EmbeddingStore` holds the committed
(Z, vol, deg) sketch, every read is O(n k_RP) streamed through the fused
distance/top-k kernel (:mod:`repro.kernels.emb_query`):

* :func:`top_anomalies_from_store` -- the k most anomalous nodes "now",
  scored by commute distance to the volume centroid ``zbar`` (the ranking is
  identical to mean commute distance to all nodes: the cross terms collapse
  to a per-query constant).  ``corrected=True`` swaps in the von Luxburg
  amplified score ``C/vol - 1/deg_i - 1/deg_j`` (arXiv 1003.1266) -- on
  large dense graphs raw commute times degenerate to the degree term, and
  the corrected scorer subtracts exactly that.
* :func:`nearest_neighbors` -- the k closest nodes to one node, self
  excluded in-kernel.
* :func:`commute_block` -- the (rows x cols) distance block for a handful of
  node pairs, indices validated (no silent clamping gathers).

Every query walks Z in row panels, one kernel call per panel, and the
per-query top-k merge runs inside the kernel -- no n-length score vector,
let alone an n x n block, is ever materialized.  The first query of an
artifact streams its panels from the store through
:class:`~repro.store.PanelPipeline` (encoded shipping: a bf16 artifact
crosses H2D at stored width and widens in VMEM).  Where the artifact fits
:func:`~repro.store.embstore.resident_budget`, that pass keeps the staged
panels and degree slices on the device as the store's
:class:`~repro.store.embstore.ResidentArtifact`, and later queries of the
same id through the same store object walk them there with no transfer
(the stream stats' ``peak_live_bytes`` counts every kept panel).  An
artifact over the budget streams on every query, with two panels plus the
O(q topk) running state on the device.  Every query runs under a ``phase("query")`` span that carries
a per-process query id (``query=<n>``, which the query's ``query.panel``,
``query.collect``, ``query.resident.fill`` and consumer-side ``pipeline.*``
spans carry too), with ``query.panel`` around each panel's dispatch,
``query.resident.fill`` around a pass that keeps its panels and
``query.collect`` around the wait for the answer.  It accounts
``query.{calls,panels,bytes_read}`` and ``query.resident.{hits,fills,bytes}``
in the process metrics registry.

``caddelag-query`` (:func:`main`) is the CLI entry over a store directory.
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import time
from dataclasses import dataclass

import numpy as np

from repro.core.embedding import validate_node_indices
from repro.obs import REGISTRY, phase, timed
from repro.obs import trace as obs_trace

__all__ = [
    "QueryResult",
    "commute_block",
    "main",
    "nearest_neighbors",
    "rank_auc",
    "top_anomalies_from_store",
]

_QUERY_IDS = itertools.count()


@dataclass
class QueryResult:
    """One answered query plus its cost telemetry."""

    idx: np.ndarray  # (k,) node ids, best first (-1 in unfilled slots)
    val: np.ndarray  # (k,) scores (raw commute or corrected, see `corrected`)
    emb_id: str
    corrected: bool
    panels: int  # Z row panels streamed
    bytes_read: int  # backing-tier bytes served (pre-decode)
    latency_ms: float


def _resolve_handle(store, emb_id: str | None):
    """An :class:`EmbeddingHandle` from a store or a handle (duck-typed).

    Handles carry their ``emb_id``; stores don't (their ``read_panel`` takes
    one as an argument -- so that name can't disambiguate).
    """
    if hasattr(store, "emb_id"):  # already a handle
        return store
    return store.latest() if emb_id is None else store.embedding(emb_id)


def _streamed_topk(
    handle,
    query_rows,
    *,
    topk: int,
    corrected: bool,
    largest: bool,
    exclude: np.ndarray | None = None,
    prefetch_depth: int | None = None,
    qid: int,
) -> tuple[np.ndarray, np.ndarray, int]:
    """One pass over the artifact's Z panels; returns (vals, ids, n_panels).
    ``query_rows(inv_deg)`` gives the query block and its 1/deg terms from
    the artifact's 1/deg vector; ``qid`` tags the pass's spans with the
    query's id.

    The running (q, topk) state threads through the kernel call per panel --
    identical shapes every call, so the whole walk reuses one compiled
    program regardless of n, and a walk over the store's resident copy
    makes the same calls on the same operands as the streamed pass that
    filled it.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.tiles import stream_stats
    from repro.kernels.emb_query import panel_topk_update, topk_init
    from repro.store.pipeline import PanelPipeline

    n, _ = handle.shape
    pr = handle.panel_rows
    topk = min(int(topk), n)
    device = jax.devices()[0]
    store = handle.store
    stats = stream_stats()
    resident = store.resident(handle.emb_id, device)
    inv_deg = handle.inv_deg() if resident is None else resident.inv_deg
    zq, inv_deg_q = query_rows(inv_deg)
    zq_dev = jnp.asarray(np.asarray(zq, np.float32))
    q = zq_dev.shape[0]
    idq = jnp.asarray(np.asarray(inv_deg_q, np.float32).reshape(q, 1))
    vol = handle.vol
    ex = jnp.asarray(
        np.full((q, 1), -1, np.int32)
        if exclude is None
        else np.asarray(exclude, np.int32).reshape(q, 1)
    )
    vals, idx = topk_init(q, topk, largest=largest)

    def update(row0, zp, idp):
        nonlocal vals, idx
        with timed("query.panel", query=qid, row0=row0):
            if idp is None:
                idp = jnp.asarray(inv_deg[None, row0 : row0 + pr])
            vals, idx = panel_topk_update(
                vals, idx, zq_dev, zp, idq, idp, vol, row0, ex,
                topk=topk, corrected=corrected, largest=largest,
            )
        return idp

    if resident is not None:
        for row0, zp, idp in resident.panels:
            update(row0, zp, idp)
        REGISTRY.inc("query.resident.hits")
        stats._note_live(resident.nbytes)
        n_panels = len(resident.panels)
    else:
        fill = store.resident_fill(handle.emb_id, device, inv_deg)
        sharding = jax.sharding.SingleDeviceSharding(device)
        n_panels = 0
        with (
            contextlib.nullcontext() if fill is None
            else timed("query.resident.fill", query=qid)
        ), PanelPipeline(
            [handle], list(range(0, n, pr)), pr,
            depth=prefetch_depth, sharding=sharding, stats=stats,
            encoded=True, span_args={"query": qid},
        ) as pipe:
            for row0, (zp,) in pipe:
                idp = update(row0, zp, None)
                if fill is not None:
                    fill.panels.append((row0, zp, idp))
                n_panels += 1
        if fill is not None:
            # Every kept panel is live at the pass's end, not the pipeline's two.
            stats._note_live(fill.nbytes)
            if store.finish_fill(fill):
                REGISTRY.add_named({
                    "query.resident.fills": 1.0,
                    "query.resident.bytes": float(fill.nbytes),
                })
    with obs_trace.span("query.collect", query=qid):
        return np.asarray(vals), np.asarray(idx), n_panels


def _run_query(kind: str, handle, fn, **span_args) -> QueryResult:
    """Shared telemetry wrapper: span, counters, latency.  ``fn(qid)`` reads
    the query's own rows as well as streaming Z, so the ``phase.query``
    span holds all of a query's host work."""
    t0 = time.perf_counter()
    m0 = REGISTRY.snapshot()
    qid = next(_QUERY_IDS)
    with phase("query", query=qid, kind=kind, emb_id=handle.emb_id, **span_args):
        vals, ids, n_panels = fn(qid)
    dt_ms = (time.perf_counter() - t0) * 1e3
    bytes_read = int(REGISTRY.delta(m0).get("stream.bytes_read", 0.0))
    REGISTRY.add_named(
        {
            "query.calls": 1.0,
            "query.panels": float(n_panels),
            "query.bytes_read": float(bytes_read),
        }
    )
    return vals, ids, n_panels, bytes_read, dt_ms


def top_anomalies_from_store(
    store,
    k: int = 10,
    *,
    emb_id: str | None = None,
    corrected: bool = False,
    prefetch_depth: int | None = None,
) -> QueryResult:
    """The k most anomalous nodes of one committed embedding artifact.

    Scores each node by its commute distance to the volume centroid ``zbar``
    (persisted with the artifact): ``vol * ||z_j - zbar||^2``, whose ranking
    equals mean commute distance to all nodes.  ``corrected=True`` scores
    ``||z_j - zbar||^2 - mean(1/deg) - 1/deg_j`` instead -- the von Luxburg
    amplified distance, which discounts the degenerate degree term that
    dominates raw commute times on large dense graphs.

    ``store`` is an :class:`~repro.store.embstore.EmbeddingStore` (serving
    ``emb_id``, default latest) or an ``EmbeddingHandle`` directly.
    """
    handle = _resolve_handle(store, emb_id)

    def run(qid):
        return _streamed_topk(
            handle,
            lambda inv_deg: (
                handle.zbar.reshape(1, -1),
                np.asarray([inv_deg.mean()], np.float32),
            ),
            topk=k, corrected=corrected, largest=True,
            prefetch_depth=prefetch_depth, qid=qid,
        )

    vals, ids, n_panels, bytes_read, dt_ms = _run_query(
        "top_anomalies", handle, run, corrected=corrected, k=k
    )
    return QueryResult(
        idx=ids[0], val=vals[0], emb_id=handle.emb_id, corrected=corrected,
        panels=n_panels, bytes_read=bytes_read, latency_ms=dt_ms,
    )


def nearest_neighbors(
    store,
    node: int,
    k: int = 10,
    *,
    emb_id: str | None = None,
    corrected: bool = False,
    prefetch_depth: int | None = None,
) -> QueryResult:
    """The k nearest (smallest commute distance) neighbors of ``node``,
    self excluded in-kernel.  Same streaming contract as
    :func:`top_anomalies_from_store`."""
    handle = _resolve_handle(store, emb_id)
    n = handle.shape[0]
    validate_node_indices("node", node, n)

    def run(qid):
        return _streamed_topk(
            handle,
            lambda inv_deg: (handle.read_rows([int(node)]), inv_deg[[int(node)]]),
            topk=min(k, n - 1), corrected=corrected, largest=False,
            exclude=np.asarray([int(node)], np.int32),
            prefetch_depth=prefetch_depth, qid=qid,
        )

    vals, ids, n_panels, bytes_read, dt_ms = _run_query(
        "nearest_neighbors", handle, run, corrected=corrected, k=k, node=int(node)
    )
    return QueryResult(
        idx=ids[0], val=vals[0], emb_id=handle.emb_id, corrected=corrected,
        panels=n_panels, bytes_read=bytes_read, latency_ms=dt_ms,
    )


def commute_block(
    store,
    rows,
    cols,
    *,
    emb_id: str | None = None,
    corrected: bool = False,
) -> np.ndarray:
    """The (rows x cols) commute-distance block from a persisted artifact.

    ``c(i, j) = vol * ||z_i - z_j||^2`` (raw) or the von Luxburg amplified
    ``||z_i - z_j||^2 - 1/deg_i - 1/deg_j`` (``corrected=True``).  Indices
    are validated -- out-of-range ids raise ``IndexError`` naming the bad
    index and n, instead of jax's silent clamping gather.  Gathers O(|rows| +
    |cols|) Z rows via host panel reads; intended for handfuls of pairs, not
    n-scale scans (those are :func:`top_anomalies_from_store`'s job).
    """
    handle = _resolve_handle(store, emb_id)
    n = handle.shape[0]
    validate_node_indices("rows", rows, n)
    validate_node_indices("cols", cols, n)
    rows = np.asarray(rows).reshape(-1)
    cols = np.asarray(cols).reshape(-1)
    zi = handle.read_rows(rows).astype(np.float64)
    zj = handle.read_rows(cols).astype(np.float64)
    dist2 = np.maximum(
        (zi * zi).sum(-1)[:, None]
        + (zj * zj).sum(-1)[None, :]
        - 2.0 * zi @ zj.T,
        0.0,
    )
    if corrected:
        inv = handle.inv_deg().astype(np.float64)
        return (dist2 - inv[rows][:, None] - inv[cols][None, :]).astype(np.float32)
    return (handle.vol * dist2).astype(np.float32)


def rank_auc(labels, scores) -> float:
    """ROC-AUC via tie-averaged ranks (dependency-free Mann-Whitney U).

    ``labels`` boolean-ish (1 = anomaly), ``scores`` higher-is-more-anomalous.
    """
    labels = np.asarray(labels).astype(bool).reshape(-1)
    scores = np.asarray(scores, np.float64).reshape(-1)
    if labels.shape != scores.shape:
        raise ValueError(f"labels {labels.shape} vs scores {scores.shape}")
    n_pos = int(labels.sum())
    n_neg = labels.size - n_pos
    if n_pos == 0 or n_neg == 0:
        raise ValueError("rank_auc needs at least one positive and one negative")
    order = np.argsort(scores, kind="mergesort")
    _, inverse, counts = np.unique(scores[order], return_inverse=True, return_counts=True)
    ends = np.cumsum(counts)
    avg_rank_per_value = (ends - counts + 1 + ends) / 2.0
    ranks = np.empty(scores.size, np.float64)
    ranks[order] = avg_rank_per_value[inverse]
    u = ranks[labels].sum() - n_pos * (n_pos + 1) / 2.0
    return float(u / (n_pos * n_neg))


# ---------------------------------------------------------------------------
# caddelag-query CLI
# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    from repro.store.embstore import EmbeddingStore

    p = argparse.ArgumentParser(
        prog="caddelag-query",
        description="Serve top-k anomaly / nearest-neighbor queries from a "
        "persisted embedding artifact (no chain build, no solve).",
    )
    p.add_argument("--store", required=True, help="EmbeddingStore directory")
    p.add_argument("--id", default=None, help="embedding id (default: latest)")
    p.add_argument("--top-k", type=int, default=10)
    p.add_argument(
        "--corrected", action="store_true",
        help="von Luxburg amplified score C/vol - 1/deg_i - 1/deg_j",
    )
    p.add_argument(
        "--neighbors", type=int, default=None, metavar="NODE",
        help="nearest neighbors of NODE instead of top anomalies",
    )
    p.add_argument("--prefetch-depth", type=int, default=None)
    args = p.parse_args(argv)

    store = EmbeddingStore.open(args.store)
    handle = _resolve_handle(store, args.id)
    print(
        f"[caddelag-query] store={args.store} id={handle.emb_id} "
        f"n={handle.shape[0]} k={handle.shape[1]} "
        f"panel_rows={handle.panel_rows} codec={store.manifest.codec} "
        f"scorer={'corrected' if args.corrected else 'raw'}"
    )
    if args.neighbors is not None:
        res = nearest_neighbors(
            handle, args.neighbors, args.top_k,
            corrected=args.corrected, prefetch_depth=args.prefetch_depth,
        )
        print(f"[caddelag-query] nearest neighbors of node {args.neighbors}:")
    else:
        res = top_anomalies_from_store(
            handle, args.top_k,
            corrected=args.corrected, prefetch_depth=args.prefetch_depth,
        )
        print("[caddelag-query] top anomalies (commute distance to centroid):")
    for rank, (i, v) in enumerate(zip(res.idx, res.val)):
        if i < 0:
            break
        print(f"  #{rank + 1:<3d} node {int(i):<8d} score {float(v):.6g}")
    print(
        f"[caddelag-query] panels={res.panels} bytes_read={res.bytes_read} "
        f"latency_ms={res.latency_ms:.2f}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
