from repro.graphs.csr import (
    Graph,
    GraphDelta,
    BlockedCOO,
    DecompositionPlan,
    EdgeList,
    build_blocked_coo,
    blocked_tile_stats,
    patch_blocked_coo,
)
from repro.graphs.rmat import rmat_graph, rmat_edge_chunks
from repro.graphs.datasets import DATASETS, make_dataset
from repro.graphs.store import (
    GraphStore,
    StoreError,
    StoreChecksumError,
    is_store,
    load_graph,
    load_store,
    save_graph,
)
from repro.graphs.pipeline import BuildConfig, run_pipeline, final_store_path
from repro.graphs.reorder import (
    ORDERS,
    compute_order,
    permute_graph,
    unpermute_ranks,
)

__all__ = [
    "Graph",
    "GraphDelta",
    "BlockedCOO",
    "DecompositionPlan",
    "EdgeList",
    "build_blocked_coo",
    "blocked_tile_stats",
    "patch_blocked_coo",
    "rmat_graph",
    "rmat_edge_chunks",
    "DATASETS",
    "make_dataset",
    "GraphStore",
    "StoreError",
    "StoreChecksumError",
    "is_store",
    "load_graph",
    "load_store",
    "save_graph",
    "BuildConfig",
    "run_pipeline",
    "final_store_path",
    "ORDERS",
    "compute_order",
    "permute_graph",
    "unpermute_ranks",
]
