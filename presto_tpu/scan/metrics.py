"""Process-wide selective-scan counters for the /v1/metrics plane.

The per-query numbers live in ExecContext.stats (keyed
"scan.<table>.<counter>"); these process totals are what a Prometheus
scraper sees on a long-lived worker/coordinator. Monotonic counters,
thread-safe (scans run on prefetch threads)."""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

COUNTER_NAMES = (
    "splits_pruned", "rows_predecode_filtered", "bytes_skipped",
    # exec-side counters exposed on the same plane-labeled family
    # (radix-partitioned breakers + join fanout estimation, PR 3)
    "join_fanout_overflow_rows", "radix_partitions_spilled",
    "radix_spill_bytes", "radix_aligned_batches",
    "join_search_steps", "join_verify_width", "join_emit_lanes",
    "join_expand_rows", "join_build_rows", "join_expand_sized",
)

# dispatch-count counters for whole-fragment fusion (exec/fragment_jit.py):
# these render as presto_tpu_{k}_total — NOT under the scan_ prefix, they
# count engine dispatches — but share the store/lock/plane-label contract
_DISPATCH_COUNTER_NAMES = (
    "fragment_dispatches", "batch_dispatches",
    # breaker-engine dispatches (exec/runtime.py): one count per breaker
    # program instantiation, labeled by the CBO's hash-vs-sort choice
    "breaker_dispatches_hash", "breaker_dispatches_sort",
    # mesh ICI exchange plane (parallel/mesh_exec.py): bytes shipped by
    # all_to_all, lane slot occupancy vs allocation (utilization =
    # used/total — a lane-sizing regression shows as the ratio dropping),
    # and surgical overflow replays
    "mesh_exchange_bytes", "mesh_exchange_lanes_used",
    "mesh_exchange_lanes_total", "mesh_exchange_overflow_retries",
    # runtime-statistics feedback plane (obs/runstats.py): every capacity
    # regrow / fanout-widening replay a breaker executed — the direct cost
    # of estimate error that HBO correction exists to eliminate
    "breaker_replay_waves",
    # those of them that re-merged a spilled aggregation's leaf
    "agg_replay_waves",
    # dynamic hybrid hash spill plane (spiller.py + exec/runtime.py):
    # partition-tree leaves created, next-hash-bits repartition events,
    # per-partition build/probe role reversals, and pool-pressure
    # revocations honored by spillable operators
    "spill_partitions", "spill_repartitions", "spill_role_reversals",
    "spill_revocations",
    # exchange plane (server/exchange.py): batches a consumer took from an
    # upstream task on its own worker in process, and pages it decoded
    "exchange_handoff_batches", "exchange_page_batches",
)

_HELP = {
    "splits_pruned": "splits eliminated by min/max split statistics",
    "rows_predecode_filtered":
        "rows dropped by host value filters before device upload",
    "bytes_skipped":
        "payload bytes never uploaded thanks to predicate-during-decode",
    "join_fanout_overflow_rows":
        "live probe rows whose run of equal hashes is wider than "
        "max_fanout_scan",
    "radix_partitions_spilled":
        "radix partitions whose build side exceeded join_spill_budget_bytes "
        "and were processed from host spill",
    "radix_spill_bytes":
        "bytes written to host spill files by radix-partitioned breakers",
    "radix_aligned_batches":
        "exchange pages consumed with a radix tag, skipping the device "
        "re-partition sort",
    "join_search_steps":
        "halving rounds a sort-engine join probe runs inside one bucket of "
        "its build's directory, summed over the builds observed "
        "(ops/join.py: search_steps)",
    "join_verify_width":
        "build lanes a sort-engine unique probe may verify past its bucket "
        "search, the widest run sharing bucket and fingerprint, summed over "
        "the builds observed (ops/join.py: verify_width)",
    "join_emit_lanes":
        "lanes of the batches a join or semi-join materialised once their "
        "live count was read: a pending join output gathered, a sparse "
        "batch compacted (exec/runtime.py: _emit_phase)",
    "join_expand_rows":
        "rows the probe batches on a join's general path (a build that "
        "fans out) expanded to: each batch's total, read once by the host "
        "(exec/runtime.py: _expand_phases)",
    "join_build_rows":
        "live rows of the sorted join builds observed, read once by the "
        "host with the build's other statistics (exec/runtime.py: "
        "_observe_build_table)",
    "join_expand_sized":
        "lanes of the first expand chunks of general-path probe batches "
        "sized by the previous batch's total below their cap "
        "(join_out_capacity or the probe's capacity), recorded with "
        "tracing on (exec/runtime.py: _expand_phases)",
    "fragment_dispatches":
        "fused whole-fragment device dispatches (one lax.scan program "
        "covering a stacked window of batches)",
    "batch_dispatches":
        "per-batch breaker step dispatches (the unfused fallback path)",
    "breaker_dispatches_hash":
        "breaker program instantiations routed to the Pallas linear-probing "
        "hash engine (ops/pallas_hash) by the CBO or a session override",
    "breaker_dispatches_sort":
        "breaker program instantiations routed to the sort/searchsorted "
        "engine (the default when stats disfavor or preclude hashing)",
    "mesh_exchange_bytes":
        "bytes shipped through mesh OUT_HASH exchange collectives "
        "(all_to_all payload, summed over devices)",
    "mesh_exchange_lanes_used":
        "occupied exchange lane row slots (rows actually routed into "
        "(src device, dst partition) lanes)",
    "mesh_exchange_lanes_total":
        "allocated exchange lane row slots (n_dev^2 x per_cap per "
        "exchange) — used/total is lane utilization",
    "mesh_exchange_overflow_retries":
        "mesh query replays triggered by a capacity-site overflow "
        "(per-site surgical retry, parallel/mesh_exec)",
    "breaker_replay_waves":
        "overflow-replay waves executed by pipeline breakers (capacity "
        "regrows and join fanout widenings) — the runtime cost of "
        "estimate error, driven to zero by hbo=correct on warm structures",
    "agg_replay_waves":
        "overflow-replay waves inside the replay of a spilled aggregation's "
        "leaf partition, whose table is sized from the leaf's row count so "
        "that none is expected (exec/runtime.py: finalize_leaf)",
    "spill_partitions":
        "spill partition-tree leaves finalized by hybrid hash join/agg "
        "replays (the dynamic partition count actually used)",
    "spill_repartitions":
        "next-hash-bits repartition events: a spill partition outgrew its "
        "budget mid-build or at replay and split into a child spiller",
    "spill_role_reversals":
        "spilled join partitions replayed with build/probe roles reversed "
        "because the nominal build side turned out larger",
    "spill_revocations":
        "memory-pool revoke requests honored by spillable operator state "
        "(accumulators / join builds spilled down at a batch boundary)",
    "exchange_handoff_batches":
        "batches an exchange consumer took in process from an upstream "
        "task's output buffer on its own worker: device batches, no page "
        "(server/exchange.py)",
    "exchange_page_batches":
        "exchange pages a consumer decoded and put on the device: from "
        "another worker, or the coordinator's result stream "
        "(server/exchange.py)",
}

_lock = threading.Lock()
_counters: Dict[str, int] = {
    k: 0 for k in COUNTER_NAMES + _DISPATCH_COUNTER_NAMES}


def record(name: str, delta: int) -> None:
    if name not in _counters or delta == 0:
        return
    with _lock:
        _counters[name] += int(delta)


def snapshot() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Test hook — zero the process counters."""
    with _lock:
        for k in _counters:
            _counters[k] = 0


def metric_rows(labels: Optional[Dict[str, str]] = None,
                ) -> List[Tuple[str, str, object, Optional[Dict[str, str]],
                                str]]:
    """Rows for server.metrics.render_metrics — always present (0 when the
    selective path never ran) so scrapers see stable families. These are
    PROCESS-wide monotonic counters: callers embedding them on an endpoint
    must label which plane is exposing them (the server metrics module
    adds plane=worker / plane=coordinator) or a single-process deployment
    scraped on both planes double-counts."""
    snap = snapshot()
    rows = [(f"presto_tpu_scan_{k}_total", _HELP[k], snap[k], labels,
             "counter")
            for k in COUNTER_NAMES]
    rows.extend((f"presto_tpu_{k}_total", _HELP[k], snap[k], labels,
                 "counter")
                for k in _DISPATCH_COUNTER_NAMES)
    return rows
