"""Task output buffers — the producer side of the HTTP pull shuffle.

Reference: execution/buffer/OutputBuffer.java and its Partitioned/Broadcast
variants + ClientBuffer: pages are buffered per downstream consumer, fetched
by explicit token sequence numbers, retained until acknowledged, so a
consumer can re-fetch from any token (restart-safe, exactly-once delivery —
TaskResource.java:245-304).

Spool mode (phased execution): a build-phase task's consumers are created
in a LATER phase, so back-pressure can never drain — pages beyond the
memory cap overflow to an unlinked temp file instead of blocking (the
reference's spooling output buffers), and `get` reads them back
transparently by token.

In-process handoff: a consumer task on the same worker claims its partition
(`claim`) when it is created and reads the buffer directly
(`get(in_process=True)`). Its entries are then the producer's device
batches themselves, not pages. A partition that neither a local consumer has
claimed nor an HTTP pull has read yet also takes device batches, up to
`_UNREAD_DEVICE_BYTES`, so that whatever the producer makes before its
consumer exists is handed too; an HTTP pull that finds them serializes them
(server/worker.py). A spooling buffer holds pages only.
"""

from __future__ import annotations

import os
import tempfile
import threading
from typing import List, Optional, Tuple

from presto_tpu.memory import batch_device_bytes

# device bytes a partition read by no one yet may hold: a local consumer
# claims its partition within a task creation of its producer's, an HTTP
# consumer may first pull only once it has read its other inputs
_UNREAD_DEVICE_BYTES = 32 << 20


def _item_bytes(item) -> int:
    """What an entry holds: a page's length, or a device batch's bytes."""
    if isinstance(item, bytes):
        return len(item)
    return batch_device_bytes(item)


class _Shared:
    """One broadcast item (a page, or a device batch handed in process)
    shared by every partition queue. The buffer's byte accounting holds it
    exactly ONCE and releases it when the last consumer acks or aborts
    (BroadcastOutputBuffer's page refcounting) — counting per partition
    overstated buffered bytes N× and tripped back-pressure long before the
    buffer was actually full."""

    __slots__ = ("item", "size", "refs")

    def __init__(self, item, size: int, refs: int):
        self.item = item
        self.size = size
        self.refs = refs


class _PartitionBuffer:
    """Token-addressed queue for one consumer. Entries are hot bytes, device
    batches, `_Shared` items or ("d", offset, length) descriptors into the
    shared spool file."""

    def __init__(self):
        self.entries: List[object] = []
        self.base_token = 0          # token of entries[0]
        self.no_more = False
        self.aborted = False
        self.local = False           # claimed by a consumer on this worker
        self.remote = False          # read by an HTTP pull
        self.unread_bytes = 0        # device bytes enqueued before either

    @property
    def end_token(self) -> int:
        return self.base_token + len(self.entries)


class OutputBuffer:
    """Pages or device batches per downstream partition with token/ack
    delivery.

    broadcast=True appends every item to all partitions (shared, not
    copied — reference: BroadcastOutputBuffer page reference counting).
    spool_dir, when set, disables producer blocking: overflow pages go to
    disk (see module docstring). in_process=False holds pages alone, for a
    buffer no consumer on this worker reads (the coordinator's result
    stream); a spooling buffer holds pages alone too.
    """

    def __init__(self, n_partitions: int, broadcast: bool = False,
                 max_buffered_bytes: int = 256 << 20,
                 spool_dir: Optional[str] = None, in_process: bool = True):
        self.n_partitions = n_partitions
        self.broadcast = broadcast
        self._parts = [_PartitionBuffer() for _ in range(n_partitions)]
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._bytes = 0
        self._spooled_bytes = 0
        self._max_bytes = max_buffered_bytes
        self._spool_dir = spool_dir
        self._pages_only = spool_dir is not None or not in_process
        self._spool_f = None  # unlinked temp file: space frees on close
        self._failed: Optional[str] = None

    # -- producer ---------------------------------------------------------

    def _spool_page(self, page: bytes):
        if self._spool_f is None:
            fd, path = tempfile.mkstemp(prefix="outbuf-", suffix=".spool",
                                        dir=self._spool_dir)
            self._spool_f = os.fdopen(fd, "wb")
            os.unlink(path)  # invisible; space reclaimed when fd closes
        off = self._spool_f.tell()
        self._spool_f.write(page)
        self._spool_f.flush()
        self._spooled_bytes += len(page)
        return ("d", off, len(page))

    def _read_entry(self, entry):
        if isinstance(entry, _Shared):
            return entry.item
        if isinstance(entry, tuple):
            _, off, length = entry
            return os.pread(self._spool_f.fileno(), length, off)
        return entry

    def _entry_bytes(self, entry) -> int:
        if isinstance(entry, _Shared):
            return entry.size
        if isinstance(entry, tuple):
            return entry[2]
        return _item_bytes(entry)

    def _release_entry(self, entry):
        # caller holds the lock
        if isinstance(entry, _Shared):
            entry.refs -= 1
            if entry.refs == 0:
                self._bytes -= entry.size
        elif not isinstance(entry, tuple):
            self._bytes -= _item_bytes(entry)

    def claim(self, partition: int) -> bool:
        """A consumer on this worker takes `partition` in process: True
        unless the buffer holds pages alone or an HTTP pull already read
        it."""
        with self._cond:
            pb = self._parts[partition]
            if not (self._pages_only or pb.remote):
                pb.local = True
            return pb.local

    def hands_off(self, partition: int) -> bool:
        """Whether the producer should enqueue its device batch for
        `partition` rather than a page: claimed in process, or read by no
        one yet and under `_UNREAD_DEVICE_BYTES`."""
        pb = self._parts[partition]
        if self._pages_only or pb.remote or pb.aborted:
            return False
        return pb.local or pb.unread_bytes < _UNREAD_DEVICE_BYTES

    def enqueue(self, partition, item):
        """Append a page, or a device batch for partitions handed in
        process. `partition` is a list of partitions, exactly those; one
        partition (all of them where the buffer broadcasts); or None, all.
        Blocks for back-pressure when the buffer is full
        (OutputBufferMemoryManager's blocked future) — unless spooling,
        where overflow pages go to disk instead."""
        with self._cond:
            if self._spool_dir is None:
                while self._bytes >= self._max_bytes and not self._all_aborted():
                    self._cond.wait(timeout=1.0)
            if isinstance(partition, (list, tuple)):
                fanout = partition
            elif self.broadcast or partition is None:
                fanout = range(self.n_partitions)
            else:
                fanout = (partition,)
            targets = [p for p in fanout if not self._parts[p].aborted]
            size = _item_bytes(item)
            entry: object = item
            if (self._spool_dir is not None
                    and self._bytes + size > self._max_bytes):
                entry = self._spool_page(item)
            elif len(targets) > 1:
                entry = _Shared(item, size, len(targets))
                self._bytes += size
            elif targets:
                self._bytes += size
            for p in targets:
                pb = self._parts[p]
                pb.entries.append(entry)
                if not (isinstance(item, bytes) or pb.local or pb.remote):
                    pb.unread_bytes += size
            self._cond.notify_all()

    def set_no_more_pages(self):
        with self._cond:
            for pb in self._parts:
                pb.no_more = True
            self._cond.notify_all()

    def fail(self, message: str):
        with self._cond:
            self._failed = message
            for pb in self._parts:
                pb.no_more = True
            self._cond.notify_all()

    def _all_aborted(self) -> bool:
        return all(pb.aborted for pb in self._parts)

    def _maybe_release_spool(self):
        # caller holds the lock; drop the spool file once no partition can
        # ever read from it again
        if self._spool_f is None:
            return
        if all(pb.aborted or (pb.no_more and not pb.entries)
               for pb in self._parts):
            try:
                self._spool_f.close()
            except OSError:
                pass
            self._spool_f = None
            self._spooled_bytes = 0

    # -- consumer ---------------------------------------------------------

    def get(self, partition: int, token: int, max_bytes: int = 16 << 20,
            max_wait_s: float = 1.0, in_process: bool = False,
            ) -> Tuple[list, int, bool]:
        """Items from `token` on (long-poll up to max_wait_s): pages, and
        device batches where the partition is handed in process or was not
        read before. `in_process=False` is an HTTP pull, after which the
        producer makes pages for the partition.

        Returns (items, next_token, complete). Re-fetching an unacked token
        returns the same items (exactly-once via client-side dedup, like
        SerializedPage token semantics)."""
        with self._cond:
            pb = self._parts[partition]
            if not in_process:
                pb.remote = True
            if self._failed is not None:
                raise BufferFailed(self._failed)
            deadline = max_wait_s
            while token >= pb.end_token and not pb.no_more and deadline > 0:
                step = min(deadline, 0.1)
                self._cond.wait(timeout=step)
                deadline -= step
                if self._failed is not None:
                    raise BufferFailed(self._failed)
            pages = []
            size = 0
            t = token
            if t < pb.base_token:
                t = pb.base_token  # already acked past this point
            while t < pb.end_token and size < max_bytes:
                entry = pb.entries[t - pb.base_token]
                pages.append(self._read_entry(entry))
                size += self._entry_bytes(entry)
                t += 1
            complete = pb.no_more and t >= pb.end_token
            return pages, t, complete

    def ack(self, partition: int, token: int):
        """Discard pages before `token` (client acknowledged receipt)."""
        with self._cond:
            pb = self._parts[partition]
            drop = min(max(token - pb.base_token, 0), len(pb.entries))
            for i in range(drop):
                self._release_entry(pb.entries[i])
            del pb.entries[:drop]
            pb.base_token += drop
            self._maybe_release_spool()
            self._cond.notify_all()

    def abort(self, partition: int):
        with self._cond:
            pb = self._parts[partition]
            pb.aborted = True
            for e in pb.entries:
                self._release_entry(e)
            pb.entries.clear()
            pb.no_more = True
            self._maybe_release_spool()
            self._cond.notify_all()

    def buffered_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def spooled_bytes(self) -> int:
        with self._lock:
            return self._spooled_bytes

    def is_finished(self) -> bool:
        with self._lock:
            return all(
                pb.aborted or (pb.no_more and not pb.entries) for pb in self._parts
            )


class BufferFailed(RuntimeError):
    pass
