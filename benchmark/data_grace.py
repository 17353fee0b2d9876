"""TPC-H's data module for a deployment whose aggregation spills: `data.py`'s
five functions, unchanged, behind one question put to the engine at import.

A cell of such a deployment can be run at all only by an engine whose
spilled aggregation mints the same programs for every seed (a leaf partition
replayed as whole batches at one capacity: `PartitioningSpiller.read_batches`,
PR 33). An engine without it answers Q18 too, exactly, but climbs a ladder
of group-table capacities in every leaf: 60 programs and about 1,600 s in
the first run on a machine at SF1, 46 and 1,100-1,150 s at SF 0.3 (PR 31's
chip runs), against the 1,200 s a run may take, and a few compiles more in
every warm run on a new seed. Such an engine is refused here, at once and by
name, instead of being killed at the limit: `run.py` imports the module the
configuration names before it makes any data.
"""

from presto_tpu.spiller import PartitioningSpiller

if not hasattr(PartitioningSpiller, "read_batches"):
    raise ImportError(
        "benchmark.data_grace: this engine replays a spilled aggregation "
        "page by page up a ladder of capacities (no PartitioningSpiller."
        "read_batches); its first run of a grace-aggregation cell takes "
        "1,100-1,600 s, past what a run may take. Not run.")

from benchmark.data import (  # noqa: E402,F401
    column_array, generate, install, referenced_bytes, scanned_rows, strings)
