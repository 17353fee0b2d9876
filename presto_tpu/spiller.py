"""Spilling: hash-partitioned batch spill files.

Reference: spiller/ (FileSingleStreamSpiller — pages serialized to a temp
file; GenericPartitioningSpiller — rows routed to per-partition spill
streams) driving SpillableHashAggregationBuilder and HashBuilderOperator's
SPILLING_INPUT state, plus the dynamic hybrid hash join literature
(arXiv 2112.02480): partition counts are ESTIMATES, and a robust spill
plane must grow them mid-build and recursively repartition oversized
spilled partitions instead of failing.

TPU-native shape: spill moves whole fixed-capacity batches HBM → host disk
using the exchange page format (serde), one crc32-guarded page per batch.
Partitioning reuses the device hash-partition kernel idea on the host: a
spilled aggregation/join partitions rows by hash(keys) % P so each
partition can later be processed independently within memory (the same
bucket-by-bucket idea as grouped execution / Lifespans). A partition that
blows past its byte budget splits by the NEXT hash bits —
(hash // divisor) % fanout — so the split uses fresh entropy and both
sides of a join stay co-partitioned as long as they split with the same
divisor/fanout schedule.
"""

from __future__ import annotations

import itertools
import os
import tempfile
import threading
import zlib
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np

from presto_tpu.batch import Batch, Column
from presto_tpu.obs import trace as _obs_trace
from presto_tpu.serde import deserialize_batch, serialize_batch

# Process-monotonic spill-file ids: `id(self)` is recycled after GC, so two
# spillers alive at different times in one query could collide on the same
# path and silently interleave pages. A counter never reuses a name.
_file_counter = itertools.count(1)


def next_file_id() -> int:
    return next(_file_counter)


class SpillCorruption(RuntimeError):
    """A spilled page failed its crc32 / framing check on replay
    (SPILL_CORRUPTION): fail loudly instead of feeding garbage rows back
    into the query."""

    def __init__(self, path: str, page: int, reason: str):
        super().__init__(
            f"spill file corruption in {path!r} at page {page}: {reason}")
        self.path = path
        self.page = page
        self.reason = reason


class SpillLimitExceeded(RuntimeError):
    """Spill could not converge within its limits (SPILL_LIMIT_EXCEEDED):
    either the spill directory's byte budget is exhausted or recursive
    repartitioning hit its depth bound without shrinking a partition
    (e.g. one-hot identical keys share every hash bit and can never
    split)."""


_PAGE_HEADER = 12  # 8-byte little-endian length + 4-byte crc32


class SpillFile:
    """Append-only page stream on disk (FileSingleStreamSpiller analog).

    Page frame: [8B length][4B crc32(payload)][payload]. The crc is
    verified on every read so disk bit-rot or a truncated write surfaces
    as a structured SpillCorruption, not silently wrong results."""

    def __init__(self, path: str, manager: Optional["SpillManager"] = None,
                 phases: Optional[str] = None):
        self.path = path
        self.manager = manager
        # "<phases>_spill_write" / "<phases>_spill_read" round every page
        # (`items` = its bytes, header included), on the current tracer;
        # None (a join's files, sort runs) records nothing
        self.phases = phases
        self._f = open(path, "wb")
        self.pages = 0
        self.bytes = 0
        self.rows = 0
        self._closed = False

    def append(self, batch: Batch, rows: Optional[int] = None):
        page = serialize_batch(batch)
        n = len(page) + _PAGE_HEADER
        if self.manager is not None:
            self.manager.charge(n)
        with _phase(self.phases, "{}_spill_write", n):
            self._f.write(len(page).to_bytes(8, "little"))
            self._f.write(zlib.crc32(page).to_bytes(4, "little"))
            self._f.write(page)
        self.pages += 1
        self.bytes += n
        if rows is None:
            rows = int(np.asarray(batch.live).sum())
        self.rows += rows

    def finish_writing(self):
        if self._f is not None:
            self._f.close()
            self._f = None

    def read(self, host: bool = False) -> Iterator[Batch]:
        """The pages in the order written; `host=True` as numpy arrays of
        each page's own rows (serde.deserialize_batch)."""
        self.finish_writing()
        if self.pages == 0:
            return
        with open(self.path, "rb") as f:
            page = 0
            while True:
                head = f.read(8)
                if len(head) == 0:
                    return
                if len(head) < 8:
                    raise SpillCorruption(self.path, page,
                                          "truncated page header")
                n = int.from_bytes(head, "little")
                with _phase(self.phases, "{}_spill_read", n + _PAGE_HEADER):
                    crc_raw = f.read(4)
                    if len(crc_raw) < 4:
                        raise SpillCorruption(self.path, page,
                                              "truncated crc")
                    payload = f.read(n)
                    if len(payload) < n:
                        raise SpillCorruption(
                            self.path, page,
                            f"truncated page: want {n} bytes, "
                            f"got {len(payload)}")
                    if zlib.crc32(payload) != int.from_bytes(crc_raw,
                                                             "little"):
                        raise SpillCorruption(self.path, page,
                                              "crc32 mismatch")
                    batch = deserialize_batch(payload, host=host)
                yield batch
                page += 1

    def close(self):
        if self._closed:
            return
        self._closed = True
        self.finish_writing()
        if self.manager is not None:
            self.manager.discharge(self.bytes)
        try:
            os.unlink(self.path)
        except OSError:
            pass


def _phase(phases: Optional[str], name: str, items: int = 0):
    """One occurrence of `name` (its `{}` filled with `phases`) on the
    thread's tracer; a spiller or file without `phases` records nothing."""
    tracer = _obs_trace.current() if phases else _obs_trace.NOOP
    return tracer.phase(name.format(phases), items=items)


def _strhash_lut(d) -> np.ndarray:
    """code+1-indexed table of string-content hashes (slot 0 = NULL)."""
    return d.content_hash_lut()


def np_row_hash(cols) -> np.ndarray:
    """THE canonical per-row content hash over host arrays; cols is a list
    of (values, dictionary|None, validity|None). String keys hash by
    CONTENT via a per-dictionary lookup table, not by code — the two sides
    of a spilled join may be encoded against different dictionaries."""
    n = len(cols[0][0])
    h = np.zeros(n, dtype=np.uint64)
    for vals, d, validity in cols:
        a = np.asarray(vals)
        if a.dtype.kind == "f":
            # hash float keys by canonical bit pattern, not by truncation
            # (astype(int64) folds every double in [0,1) onto 0 — a
            # degenerate bucketing that recursive repartitioning can
            # never split). Canonicalize -0.0 and NaN so equal groups
            # always share a bucket.
            a = a.astype(np.float64)
            a = np.where(a == 0.0, np.float64(0.0), a)
            a = np.where(np.isnan(a), np.float64("nan"), a)
            v = a.view(np.int64)
        else:
            v = a.astype(np.int64)
        if d is not None:
            v = _strhash_lut(d)[v + 1]
        if validity is not None:
            v = np.where(np.asarray(validity), v, np.int64(-0x61c88647))
        h = (h * np.uint64(0x9E3779B185EBCA87)) ^ v.astype(np.uint64)
        h = h ^ (h >> np.uint64(31))
    return h


def _est_row_bytes(batch: Batch) -> int:
    """Per-row DEVICE byte estimate for replay budgeting. Neither disk nor
    page bytes predict what a replayed partition costs in device memory:
    serialized pages carry framing + schema + (for string columns) the
    whole dictionary, which is a SHARED host-side object — a partition
    split by fresh hash bits halves its rows but not its embedded
    dictionary copies, so a disk-byte budget could recurse forever without
    converging. rows × dtype-width converges by construction."""
    w = 0
    for c in batch.columns:
        w += np.dtype(c.values.dtype).itemsize
        for plane in (c.validity, c.hi, c.sizes, c.evalid, c.keys):
            if plane is not None:
                w += np.dtype(plane.dtype).itemsize
    return max(1, w)


def np_bucket_ids(cols, n_buckets: int, divisor: int = 1) -> np.ndarray:
    """Row → bucket id over host arrays. THE canonical content-hash
    bucketing: the spiller, the bucketed-table writer, and colocated-join
    split placement must all agree on it (the reference's
    HiveBucketing.getHiveBucket contract), so bucket b of one table only
    ever joins bucket b of another.

    `divisor` consumes already-spent hash entropy: a level-ℓ sub-partition
    routes by (hash // divisor) % n_buckets where divisor is the product
    of the fanouts above it, so recursive repartitioning always splits on
    FRESH bits and co-partitioned pairs that split with the same schedule
    stay aligned."""
    h = np_row_hash(cols)
    if divisor > 1:
        h = h // np.uint64(divisor)
    return (h % np.uint64(n_buckets)).astype(np.int64)


_PLANES = ("values", "validity", "hi", "sizes", "evalid", "keys")


def _page_shape(page: Batch) -> tuple:
    """What two host pages must share to be concatenated plane by plane:
    schema, dictionaries (interned by content, so identity is equality),
    which planes each column carries and a structural column's width."""
    return (page.names, tuple(str(t) for t in page.types),
            tuple(sorted((k, id(d)) for k, d in page.dicts.items())),
            tuple(tuple(None if getattr(c, f) is None
                        else (getattr(c, f).dtype.str,
                              getattr(c, f).shape[1:])
                        for f in _PLANES) for c in page.columns))


def pack_pages(pages: Iterator[Batch], capacity: int) -> Iterator[Batch]:
    """Host pages (`SpillFile.read(host=True)`: numpy planes of each page's
    own rows, every row live) packed into device batches of exactly
    `capacity`: full ones while rows last, then one padded with dead rows.
    Every batch of a run has the same shape whatever the pages' sizes
    were, so the programs that merge them do not depend on the data. A
    page that cannot be concatenated with the run before it (another
    dictionary, a wider array column) closes that run with a padded batch
    and starts the next at the same capacity."""
    import jax.numpy as jnp

    def rows_of(run: List[Batch], lo: int, hi: int, put) -> List[Column]:
        """Rows [lo, hi) of the run's concatenation, plane by plane."""
        return [Column(*(
            None if getattr(c0, f) is None else put(np.concatenate(
                [getattr(pg.columns[i], f) for pg in run])[lo:hi])
            for f in _PLANES)) for i, c0 in enumerate(run[0].columns)]

    def padded(rows: np.ndarray):
        buf = np.zeros((capacity,) + rows.shape[1:], rows.dtype)
        buf[:len(rows)] = rows
        return jnp.asarray(buf)

    def drain(run: List[Batch], final: bool) -> Iterator[Batch]:
        """Full batches off the front of `run`, with `final` the rest too;
        what is not emitted stays in `run` as one page."""
        rows = sum(pg.capacity for pg in run)
        lo = 0
        while rows - lo >= capacity or (final and lo < rows):
            hi = min(lo + capacity, rows)
            yield Batch(run[0].names, run[0].types,
                        rows_of(run, lo, hi, padded),
                        padded(np.ones(hi - lo, dtype=bool)), run[0].dicts)
            lo = hi
        if lo:
            run[:] = [Batch(run[0].names, run[0].types,
                            rows_of(run, lo, rows, lambda a: a),
                            np.ones(rows - lo, dtype=bool),
                            run[0].dicts)] if lo < rows else []

    run: List[Batch] = []
    for page in pages:
        if run and _page_shape(page) != _page_shape(run[0]):
            yield from drain(run, final=True)
        run.append(page)
        if sum(pg.capacity for pg in run) >= capacity:
            yield from drain(run, final=False)
    yield from drain(run, final=True)


class PartitioningSpiller:
    """Routes batch rows to per-partition spill files by hash(keys)
    (GenericPartitioningSpiller analog), with dynamic hybrid-hash growth:
    a partition whose file crosses `partition_budget_bytes` splits by the
    next hash bits into a child spiller mid-build, and the replay drivers
    can force the same split (`grow_partition`) on a spilled partition
    whose replay would not fit the memory budget. Leaves of the resulting
    tree are the units of replay; `leaf_items()` walks them.

    Routing hashes string keys by CONTENT (via a per-dictionary lookup
    table), not by dictionary code — the two sides of a spilled join may be
    encoded against different dictionaries, and co-partitioning must agree
    on the string value itself."""

    def __init__(self, spill_dir: str, key_names: Sequence[str],
                 n_partitions: int, tag: str = "spill",
                 divisor: int = 1, depth: int = 0,
                 manager: Optional["SpillManager"] = None,
                 partition_budget_bytes: Optional[int] = None,
                 max_depth: int = 0,
                 on_grow: Optional[Callable[["PartitioningSpiller", int],
                                            None]] = None,
                 on_spill: Optional[Callable[[int, int], None]] = None,
                 phases: Optional[str] = None):
        self.spill_dir = spill_dir
        self.key_names = tuple(key_names)
        self.n_partitions = n_partitions
        self.tag = tag
        self.divisor = divisor
        self.depth = depth
        self.manager = manager
        self.partition_budget_bytes = partition_budget_bytes
        self.max_depth = max_depth
        self.on_grow = on_grow
        # batch-boundary telemetry hook (obs/inflight plane): called
        # (spilled_bytes, max_leaf_depth) after each routed batch on the
        # ROOT spiller only — children report through their root
        self.on_spill = on_spill
        # an aggregate's spiller names its phases ("agg"): the read of a
        # routed batch's keys is "host_sync:<phases>_spill_rows", its
        # files' pages "<phases>_spill_write" / "_read" (SpillFile);
        # children inherit it
        self.phases = phases
        # per-row device-byte width (schema-static), estimated lazily from
        # the first spilled batch and inherited by children on grow
        self._row_width: Optional[int] = None
        self.children: Dict[int, "PartitioningSpiller"] = {}
        self.files: List[SpillFile] = [
            SpillFile(os.path.join(
                spill_dir, f"{tag}-p{p}-{next_file_id()}.bin"),
                manager=manager, phases=phases)
            for p in range(n_partitions)
        ]

    def _pull_keys(self, batch: Batch):
        """The key columns and `live` as host arrays: the one device-to-host
        read of routing a batch (nothing moves for a page read back)."""
        with _phase(self.phases, "host_sync:{}_spill_rows"):
            keys = [(np.asarray(batch.column(k).values), batch.dicts.get(k),
                     None if batch.column(k).validity is None
                     else np.asarray(batch.column(k).validity))
                    for k in self.key_names]
            return keys, np.asarray(batch.live)

    def spill(self, batch: Batch):
        if self._row_width is None:
            self._row_width = _est_row_bytes(batch)
        keys, live = self._pull_keys(batch)
        pid = np_bucket_ids(keys, self.n_partitions, divisor=self.divisor)
        for p in range(self.n_partitions):
            mask = live & (pid == p)
            if not mask.any():
                continue
            sub = batch.with_live(mask)
            child = self.children.get(p)
            if child is not None:
                child.spill(sub)
                continue
            self.files[p].append(sub, rows=int(mask.sum()))
            # dynamic growth: the partition blew past its replay budget
            # mid-build — split it by the next hash bits instead of letting
            # one hot partition force an oversized replay later
            if (self.partition_budget_bytes is not None
                    and self.depth < self.max_depth
                    and self.files[p].rows * self._row_width
                    > self.partition_budget_bytes):
                self.grow_partition(p)
        if self.on_spill is not None:
            try:
                self.on_spill(self.spilled_bytes, self.max_leaf_depth())
            except Exception:
                pass

    def spill_unpartitioned(self, batch: Batch):
        """Whole-batch append to partition 0 (single-stream mode: sort runs,
        no co-partitioning requirement)."""
        self.files[0].append(batch)

    def grow_partition(self, p: int,
                       fanout: Optional[int] = None) -> "PartitioningSpiller":
        """Split partition p by the next hash bits into a child spiller:
        the on-disk file re-partitions into `fanout` sub-files and future
        rows routed to p flow to the child. Returns the child (idempotent:
        an existing child is returned as-is)."""
        child = self.children.get(p)
        if child is not None:
            return child
        fanout = fanout or self.n_partitions
        child = PartitioningSpiller(
            self.spill_dir, self.key_names, fanout,
            tag=f"{self.tag}-p{p}",
            divisor=self.divisor * self.n_partitions,
            depth=self.depth + 1, manager=self.manager,
            partition_budget_bytes=self.partition_budget_bytes,
            max_depth=self.max_depth, on_grow=self.on_grow,
            phases=self.phases)
        child._row_width = self._row_width
        self.children[p] = child
        for b in self.files[p].read(host=True):  # host to host: no upload
            child.spill(b)
        self.files[p].close()
        if self.on_grow is not None:
            try:
                self.on_grow(child, p)
            except Exception:
                pass
        return child

    def align_to(self, other: "PartitioningSpiller"):
        """Mirror `other`'s split tree onto this spiller (same fanouts, so
        hash schedules agree): co-partitioned pairs — a join's build and
        probe spillers — must expose IDENTICAL leaf sets or replay would
        pair a leaf of one with an ancestor of the other."""
        for p, oc in other.children.items():
            child = self.children.get(p)
            if child is None:
                child = self.grow_partition(p, fanout=oc.n_partitions)
            child.align_to(oc)

    def read_partition(self, p: int, host: bool = False) -> Iterator[Batch]:
        child = self.children.get(p)
        if child is not None:
            for q in range(child.n_partitions):
                yield from child.read_partition(q, host)
            return
        yield from self.files[p].read(host)

    def read_batches(self, p: int, capacity: int) -> Iterator[Batch]:
        """Partition p as whole batches of ONE capacity (`pack_pages`): a
        page is what one input batch left in this partition, so a replay
        that merged page by page paid one merge, and one program shape,
        for every few thousand rows."""
        return pack_pages(self.read_partition(p, host=True), capacity)

    def partition_bytes(self, p: int) -> int:
        child = self.children.get(p)
        if child is not None:
            return child.spilled_bytes
        return self.files[p].bytes

    def partition_rows(self, p: int) -> int:
        child = self.children.get(p)
        if child is not None:
            return sum(child.partition_rows(q)
                       for q in range(child.n_partitions))
        return self.files[p].rows

    def partition_est_bytes(self, p: int) -> int:
        """Estimated DEVICE bytes of replaying partition p (rows × schema
        row width) — the number replay budgets compare against; disk bytes
        over-count shared dictionaries (see _est_row_bytes)."""
        return self.partition_rows(p) * (self._row_width or 0)

    def leaf_items(self) -> Iterator[tuple]:
        """Depth-first (spiller, partition) walk of the replay units."""
        for p in range(self.n_partitions):
            child = self.children.get(p)
            if child is not None:
                yield from child.leaf_items()
            else:
                yield self, p

    def leaf_count(self) -> int:
        return sum(1 for _ in self.leaf_items())

    def max_leaf_depth(self) -> int:
        return max(sp.depth for sp, _ in self.leaf_items())

    @property
    def spilled_bytes(self) -> int:
        return (sum(f.bytes for f in self.files)
                + sum(c.spilled_bytes for c in self.children.values()))

    @property
    def spilled_pages(self) -> int:
        return (sum(f.pages for f in self.files)
                + sum(c.spilled_pages for c in self.children.values()))

    def close(self):
        for f in self.files:
            f.close()
        for c in self.children.values():
            c.close()


class SpillManager:
    """Factory + accounting for a worker's spill directory
    (SpillSpaceTracker analog). `budget_bytes` caps the directory's live
    byte footprint: a charge that would cross it fails the spilling query
    with SpillLimitExceeded instead of filling the disk."""

    def __init__(self, spill_dir: Optional[str] = None,
                 budget_bytes: Optional[int] = None):
        self._dir = spill_dir
        self._tmp = None
        self._lock = threading.Lock()
        self.total_spilled_bytes = 0
        self.spill_count = 0
        self.budget_bytes = budget_bytes
        self.in_use_bytes = 0  # live (unclosed) spill-file bytes

    @property
    def dir(self) -> str:
        with self._lock:
            if self._dir is None:
                self._tmp = tempfile.TemporaryDirectory(prefix="presto-tpu-spill-")
                self._dir = self._tmp.name
            return self._dir

    def spill_file(self, tag: str = "spill") -> SpillFile:
        """A single uniquely-named page stream charged to this manager."""
        return SpillFile(
            os.path.join(self.dir, f"{tag}-{next_file_id()}.bin"),
            manager=self)

    def partitioning_spiller(self, key_names: Sequence[str], n_partitions: int,
                             tag: str = "spill",
                             partition_budget_bytes: Optional[int] = None,
                             max_depth: int = 0,
                             on_grow=None, on_spill=None,
                             phases: Optional[str] = None,
                             ) -> PartitioningSpiller:
        d = self.dir
        with self._lock:
            self.spill_count += 1
        return PartitioningSpiller(
            d, key_names, n_partitions, tag, manager=self,
            partition_budget_bytes=partition_budget_bytes,
            max_depth=max_depth, on_grow=on_grow, on_spill=on_spill,
            phases=phases)

    def charge(self, bytes_: int):
        with self._lock:
            if (self.budget_bytes is not None
                    and self.in_use_bytes + bytes_ > self.budget_bytes):
                raise SpillLimitExceeded(
                    f"spill directory byte budget exceeded: "
                    f"{self.in_use_bytes} in use + {bytes_} requested > "
                    f"{self.budget_bytes} budget")
            self.in_use_bytes += bytes_

    def discharge(self, bytes_: int):
        with self._lock:
            self.in_use_bytes = max(0, self.in_use_bytes - bytes_)

    def record(self, bytes_: int):
        with self._lock:
            self.total_spilled_bytes += bytes_
