"""Core library: the paper's copy-detection algorithms in JAX.

Public API:
  CopyConfig, ClaimsDataset, DetectionResult    — data model
  DetectionEngine                               — THE detection entry point
                                                  (tiled + sharded; all modes)
  pairwise_detect                               — exhaustive baseline (§II-B)
  build_index, bucketize                        — inverted index (§III)
  index_detect_exact, bucketed_index_detect     — INDEX (§III)
  bound_detect, hybrid_detect                   — BOUND/BOUND+/HYBRID (§IV)
  make_incremental_state, incremental_detect    — INCREMENTAL (§V)
  truth_finding                                 — iterative fusion driver
  sample_by_item, sample_by_cell, scale_sample  — sampling (§VI)
  fagin_input                                   — NRA baseline (Table X)
  DetectRequest, DetectionService, serve_batch  — batched serving (DESIGN §5)
  CorpusStore, engine_order, ResidentCorpus     — chunked incidence store +
                                                  resident serving buffers
                                                  (DESIGN §6)
  ShardPlan, ShardedCorpusStore, shard_store    — row-range-sharded corpus
                                                  data plane: per-shard row
                                                  slices, spill/bitpack,
                                                  exact partial merge
                                                  (DESIGN §10)
  DurabilityOptions, CommitLog, RestoreInfo     — commit-log persistence +
                                                  snapshot/restore (DESIGN §8,
                                                  OPERATIONS.md)
  retract_rows, RetractInfo, RetractRecord      — source retraction: unwind
                                                  membership, GC orphans,
                                                  WAL replay (DESIGN §9.4)
  CircuitBreaker, DeadlineExceeded              — traffic hardening: commit
                                                  circuit breaker, deadline
                                                  admission/expiry (DESIGN §9)

The per-algorithm functions remain as references and compatibility wrappers;
new code should construct a ``DetectionEngine`` with the mode it needs (or a
``DetectionService`` for concurrent corpus queries).
"""
from repro.core.bound import bound_detect, hybrid_detect
from repro.core.bucketed import bucketed_index_detect, index_detect_exact
from repro.core.engine import DetectionEngine, EngineOptions
from repro.core.fagin import fagin_input
from repro.core.incremental import incremental_detect, make_incremental_state
from repro.core.index import (
    CommitInfo,
    RetractInfo,
    build_index,
    bucketize,
    commit_rows,
    compact_index,
    engine_order,
    retract_rows,
    rollback_commit,
)
from repro.core.sampling import sample_by_cell, sample_by_item, scale_sample
from repro.core.scoring import pairwise_detect, rescore_pairs_exact
from repro.core.serving import (
    CircuitBreaker,
    DeadlineExceeded,
    DetectionService,
    DetectRequest,
    DetectResponse,
    ReplicaBroadcastError,
    ReplicaRouter,
    ResidentCorpus,
    ResultCache,
    ServiceOverloaded,
    ServiceStopped,
    serve_batch,
)
from repro.core.shardplan import (
    SealedShardError,
    ShardPlan,
    ShardScanError,
    ShardedCorpusStore,
    SpillCorruptionError,
    make_shard_plan,
    merge_shard_partials,
    rebalance_plan,
    shard_store,
)
from repro.core.store import (
    CorpusStore,
    PackedBlock,
    pack_membership,
    packed_count_matmul,
    unpack_membership,
)
from repro.core.wal import (
    CommitLog,
    CommitRecord,
    DurabilityOptions,
    NoValidSnapshotError,
    ReplayDivergenceError,
    RestoreInfo,
    RetractRecord,
)
from repro.core.truthfind import fusion_accuracy, truth_finding
from repro.core.types import (
    ClaimsDataset,
    CopyConfig,
    DetectionResult,
    claim_value_keys,
    pair_f_measure,
)

__all__ = [
    "CopyConfig", "ClaimsDataset", "DetectionResult", "pair_f_measure",
    "claim_value_keys",
    "DetectionEngine", "EngineOptions", "CorpusStore",
    "ShardPlan", "ShardedCorpusStore", "shard_store", "make_shard_plan",
    "rebalance_plan", "merge_shard_partials", "ShardScanError",
    "SealedShardError", "SpillCorruptionError",
    "PackedBlock", "pack_membership", "unpack_membership",
    "packed_count_matmul",
    "DetectRequest", "DetectResponse", "DetectionService", "ReplicaRouter",
    "ReplicaBroadcastError", "ResidentCorpus", "ResultCache", "serve_batch",
    "CircuitBreaker", "DeadlineExceeded", "ServiceOverloaded",
    "ServiceStopped",
    "DurabilityOptions", "CommitLog", "CommitRecord", "RestoreInfo",
    "NoValidSnapshotError", "ReplayDivergenceError", "RetractRecord",
    "pairwise_detect", "build_index", "bucketize", "engine_order",
    "commit_rows", "rollback_commit", "compact_index", "CommitInfo",
    "retract_rows", "RetractInfo",
    "index_detect_exact", "bucketed_index_detect",
    "bound_detect", "hybrid_detect",
    "make_incremental_state", "incremental_detect", "rescore_pairs_exact",
    "truth_finding", "fusion_accuracy",
    "sample_by_item", "sample_by_cell", "scale_sample",
    "fagin_input",
]
