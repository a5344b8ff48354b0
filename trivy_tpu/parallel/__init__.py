"""Mesh-parallel execution: the TPU-native replacement for the
reference's goroutine fan-out (pkg/parallel/pipeline.go) per SURVEY.md
§2.7 — candidate pairs shard over `dp`, the advisory table shards over
`db` (the framework's tensor-parallel axis), secret byte-chunks shard
over `dp` as the sequence axis."""

from .mesh import (MeshDetector, QueryPartition,  # noqa: F401
                   ShardedTable, best_db_shards, make_mesh,
                   mesh_from_devices, partition_queries, shard_table,
                   sharded_csr_join)
