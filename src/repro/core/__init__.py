"""CADDeLaG core: distributed commute-time anomaly detection in JAX.

Public API re-exports.
"""

from repro.core.cad import CADResult, detect_anomalies, node_anomaly_scores, top_anomalies
from repro.core.chain import (
    ChainOperator,
    chain_build_count,
    chain_product,
    factored_chain,
    reset_chain_build_count,
)
from repro.core.delta_chain import (
    BaseChain,
    build_base_chain,
    full_build_gemm_cost,
    truncate_factors,
    try_delta_update,
)
from repro.core.distmatrix import (
    SCHEDULES,
    DistContext,
    build_from_nodes,
    make_context,
    matmul,
    matmul_rowblock,
    trivial_context,
)
from repro.core.embedding import (
    CommuteConfig,
    Embedding,
    commute_distance_block,
    commute_time_embedding,
    edge_projection,
    exact_commute_distances,
    validate_node_indices,
)
from repro.core.query import (
    QueryResult,
    commute_block,
    nearest_neighbors,
    rank_auc,
    top_anomalies_from_store,
)
from repro.core.sequence import SequenceDetector, SequenceResult, detect_sequence_anomalies
from repro.core.solver import estimate_solution, residual_norm
from repro.core.solvers import SolveReport, SolverSpec, estimate_rho, solve
from repro.core.tiles import (
    ProgramCacheStats,
    StreamStats,
    Tile,
    clear_program_cache,
    is_streamable,
    program_cache_stats,
    reset_program_cache_stats,
    reset_stream_stats,
    stream_stats,
    tile_map,
    tile_stream,
)

__all__ = [
    "BaseChain",
    "CADResult",
    "ChainOperator",
    "build_base_chain",
    "full_build_gemm_cost",
    "truncate_factors",
    "try_delta_update",
    "CommuteConfig",
    "ProgramCacheStats",
    "clear_program_cache",
    "program_cache_stats",
    "reset_program_cache_stats",
    "DistContext",
    "Embedding",
    "SCHEDULES",
    "SequenceDetector",
    "SequenceResult",
    "SolveReport",
    "SolverSpec",
    "StreamStats",
    "Tile",
    "build_from_nodes",
    "chain_build_count",
    "chain_product",
    "factored_chain",
    "commute_distance_block",
    "commute_time_embedding",
    "detect_anomalies",
    "detect_sequence_anomalies",
    "edge_projection",
    "estimate_rho",
    "estimate_solution",
    "exact_commute_distances",
    "is_streamable",
    "make_context",
    "matmul",
    "matmul_rowblock",
    "node_anomaly_scores",
    "QueryResult",
    "commute_block",
    "nearest_neighbors",
    "rank_auc",
    "top_anomalies_from_store",
    "validate_node_indices",
    "reset_chain_build_count",
    "reset_stream_stats",
    "residual_norm",
    "solve",
    "stream_stats",
    "tile_map",
    "tile_stream",
    "top_anomalies",
    "trivial_context",
]
