"""repro.core — the Parallel Semi-Asymmetric Model (PSAM) graph engine.

Public surface:
  CSRGraph / build_csr / graph_spec       — immutable blocked CSR (large memory)
  CompressedCSR / compress                — delta-packed execution backend (§5.1.3)
  GraphBackend / GraphLike                — the protocol both backends satisfy
  ExecutionPlan / make_plan / ShardedGraph— unified planner: one edgeMap,
                                            single-device or sharded mesh
  compact_live_blocks                     — drop filter-dead blocks before
                                            the shard split (PSAM streaming)
  VertexSubset / from_indices / from_mask — frontiers (O(n) small memory)
  edgemap_reduce / edge_map               — direction-optimized edgeMapChunked
  GraphFilter / make_filter / pack_vertices / filter_edges — §4.2 bitset filter
  Buckets / make_buckets                  — semi-eager bucketing (App. B)
  PSAMCost                                — §3 cost accounting
  TenantLedger / TenantLedgers            — per-tenant edge-read token buckets
  edgemap_round_read_words                — one dense round's read quantum
"""
from .backend import GraphBackend, GraphLike, dense_block_view, tile_block_reader
from .bucketing import NULL_BUCKET, Buckets, make_buckets
from .compressed import (
    CompressedCSR,
    compress,
    decode_block,
    decode_block_tile,
    decode_blocks,
    edgemap_sum_compressed,
)
from .csr import DEFAULT_BLOCK_SIZE, CSRGraph, build_csr, graph_spec
from .edgemap import (
    edge_map,
    edge_map_batched,
    edgemap_chunked,
    edgemap_dense,
    edgemap_dense_batched,
    edgemap_reduce,
    edgemap_reduce_batched,
)
from .graph_filter import (
    GraphFilter,
    edge_active_flat,
    edge_active_words,
    filter_edges,
    filter_edges_pred,
    live_block_indices,
    make_filter,
    pack_bits,
    pack_vertices,
    unpack_bits,
    unpack_word_bits,
)
from .plan import (
    ExecutionPlan,
    ShardedEdgeActive,
    ShardedGraph,
    compact_live_blocks,
    make_plan,
    round_loop,
    shard_edge_active,
    sharded_edgemap_reduce,
    sharded_edgemap_reduce_batched,
    sharded_graph_spec,
)
from .psam import PSAMCost, TenantLedger, TenantLedgers, edgemap_round_read_words
from .vertex_subset import VertexSubset, empty, from_indices, from_mask, full

__all__ = [
    "CompressedCSR",
    "ExecutionPlan",
    "ShardedEdgeActive",
    "ShardedGraph",
    "compact_live_blocks",
    "make_plan",
    "round_loop",
    "shard_edge_active",
    "sharded_edgemap_reduce",
    "sharded_graph_spec",
    "GraphBackend",
    "GraphLike",
    "compress",
    "decode_blocks",
    "decode_block",
    "decode_block_tile",
    "dense_block_view",
    "tile_block_reader",
    "edgemap_sum_compressed",
    "CSRGraph",
    "build_csr",
    "graph_spec",
    "DEFAULT_BLOCK_SIZE",
    "VertexSubset",
    "from_indices",
    "from_mask",
    "full",
    "empty",
    "edge_map",
    "edge_map_batched",
    "edgemap_reduce",
    "edgemap_reduce_batched",
    "edgemap_dense",
    "edgemap_dense_batched",
    "edgemap_chunked",
    "sharded_edgemap_reduce_batched",
    "GraphFilter",
    "make_filter",
    "pack_vertices",
    "filter_edges",
    "filter_edges_pred",
    "unpack_bits",
    "unpack_word_bits",
    "pack_bits",
    "edge_active_flat",
    "edge_active_words",
    "live_block_indices",
    "Buckets",
    "make_buckets",
    "NULL_BUCKET",
    "PSAMCost",
    "TenantLedger",
    "TenantLedgers",
    "edgemap_round_read_words",
]
