"""Relational kernels on fixed-capacity batches.

The analog of presto-main's hot operator internals (MultiChannelGroupByHash,
PagesHash/JoinHash, PagesIndex sort, PartitionedOutputOperator.partitionPage),
re-expressed as static-shape XLA programs: sorting + segment ops instead of
pointer-chasing hash tables, masks instead of selection vectors. A join
probe reaches its rows of the sorted build through a directory of hash
buckets (one gather of a bucket's ends and a few halvings inside it), not
through bucket chains and not through a binary search of the whole build.
"""

from presto_tpu.ops.hashing import hash_columns
from presto_tpu.ops.grouping import grouped_merge
from presto_tpu.ops.sort import sort_batch, compact
from presto_tpu.ops.join import build_side, probe_unique, probe_counts, probe_expand
from presto_tpu.ops.partition import partition_for_exchange

__all__ = [
    "hash_columns",
    "grouped_merge",
    "sort_batch",
    "compact",
    "build_side",
    "probe_unique",
    "probe_counts",
    "probe_expand",
    "partition_for_exchange",
]
