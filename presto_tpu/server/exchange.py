"""Exchange client — the consumer side of the HTTP pull shuffle.

Reference: operator/ExchangeClient.java:69 (addLocation:158, pollPage:250,
scheduleRequestIfNecessary:326) + HttpPageBufferClient.java:88: concurrent
page pulls from every upstream task's buffer, explicit token sequence
numbers, acknowledge-after-receive, bounded client-side buffer for
back-pressure.

Response wire format (mirrors PagesResponseWriter):
    <u32 header_len> <json header {next_token, complete, page_lens,
                                   task_state, error}> <pages bytes...>

An upstream task on the consumer's own worker is read in process instead,
with the same token/ack loop over its OutputBuffer (`_BufferPuller`): what
comes out is the device batch the producer handed, not a page.
"""

from __future__ import annotations

import json
import queue
import struct
import threading
import urllib.error
import urllib.request
from typing import Dict, Iterator, List, Optional

from presto_tpu.batch import Batch
from presto_tpu.scan import metrics as _scan_metrics
from presto_tpu.serde import deserialize_batch
from presto_tpu.server.buffers import BufferFailed


class ExchangeFailure(RuntimeError):
    """`task_error=True` means the REMOTE task reported a deterministic
    failure (its error message travels in the results header) — retrying
    the query would hit the same error. False means a transport-level
    failure (unreachable/partial producer), which IS worth a retry."""

    def __init__(self, msg: str, task_error: bool = False):
        super().__init__(msg)
        self.task_error = task_error
    pass


def parse_results_payload(data: bytes):
    (hlen,) = struct.unpack_from("<I", data, 0)
    header = json.loads(data[4:4 + hlen])
    pages = []
    off = 4 + hlen
    for n in header.get("page_lens", []):
        pages.append(data[off:off + n])
        off += n
    return header, pages


def encode_results_payload(header: dict, pages: List[bytes]) -> bytes:
    header = dict(header)
    header["page_lens"] = [len(p) for p in pages]
    hj = json.dumps(header, separators=(",", ":")).encode()
    return struct.pack("<I", len(hj)) + hj + b"".join(pages)


class _LocationPuller(threading.Thread):
    """One sequential token/ack pull loop per upstream location
    (HttpPageBufferClient analog)."""

    def __init__(self, location: str, out: "ExchangeClient"):
        super().__init__(daemon=True, name=f"exchange-{location}")
        self.location = location.rstrip("/")
        self.out = out

    def run(self):
        token = 0
        try:
            while not self.out.closed:
                got = self._fetch(token)
                if got is None:
                    continue
                items, next_token, complete = got
                for p in items:
                    self.out._offer(p)
                if items:
                    # acknowledge so the producer can release them
                    self._ack(next_token)
                token = next_token
                if complete:
                    break
        except Exception as e:  # propagate to the consuming iterator
            self.out._fail(f"{self.location}: {e}",
                           getattr(e, "task_error", False))
        finally:
            self.out._done()

    def _fetch(self, token: int):
        """(items, next token, complete) from `token` on, or None to ask
        again."""
        url = f"{self.location}/{token}"
        try:
            with urllib.request.urlopen(url, timeout=30) as r:
                data = r.read()
        except urllib.error.HTTPError as e:
            if e.code == 404:
                # task not created yet — transient during scheduling
                import time

                time.sleep(0.05)
                return None
            raise
        header, pages = parse_results_payload(data)
        if header.get("error"):
            raise ExchangeFailure(header["error"], task_error=True)
        return pages, header["next_token"], header.get("complete")

    def _ack(self, token: int):
        urllib.request.urlopen(f"{self.location}/{token}/ack",
                               timeout=30).read()


class _BufferPuller(_LocationPuller):
    """The same loop over an upstream task's OutputBuffer on this worker,
    whose partition the consumer claimed: device batches, no HTTP."""

    def __init__(self, location: str, buffer, partition: int,
                 out: "ExchangeClient"):
        super().__init__(location, out)
        self.buffer = buffer
        self.partition = partition

    def _fetch(self, token: int):
        try:
            return self.buffer.get(self.partition, token, in_process=True)
        except BufferFailed as e:
            # as the HTTP path reports it: the producer's own error
            raise ExchangeFailure(str(e), task_error=True) from e

    def _ack(self, token: int):
        self.buffer.ack(self.partition, token)


class ExchangeClient:
    """Pulls pages from N upstream locations concurrently, yields Batches.

    `local` maps the locations whose producer runs on this worker to its
    (OutputBuffer, partition), read in process (`_BufferPuller`)."""

    def __init__(self, locations: List[str], max_buffered_pages: int = 64,
                 local: Optional[Dict[str, tuple]] = None):
        self.locations = list(locations)
        local = local or {}
        self._queue: queue.Queue = queue.Queue(maxsize=max_buffered_pages)
        self._remaining = len(self.locations)
        self._lock = threading.Lock()
        self._error: Optional[str] = None
        self.closed = False
        self._pullers = [
            _BufferPuller(loc, *local[loc], self) if loc in local
            else _LocationPuller(loc, self) for loc in self.locations]
        for p in self._pullers:
            p.start()

    def _offer(self, page: bytes):
        while not self.closed:
            try:
                self._queue.put(page, timeout=0.5)
                return
            except queue.Full:
                continue

    def _fail(self, msg: str, task_error: bool = False):
        with self._lock:
            if self._error is None:
                self._error = msg
                self._error_is_task = task_error

    def _done(self):
        with self._lock:
            self._remaining -= 1
        self._queue.put(None)  # wake consumer

    def pages(self) -> Iterator[object]:
        """Each upstream item as it arrives: a page, or a device batch
        handed in process; `decode` makes either a Batch."""
        done = 0
        while True:
            with self._lock:
                if self._error is not None:
                    self.closed = True
                    raise ExchangeFailure(
                        self._error,
                        task_error=getattr(self, "_error_is_task", False))
                if done >= len(self.locations) and self._queue.empty():
                    return
            try:
                item = self._queue.get(timeout=0.5)
            except queue.Empty:
                continue
            if item is None:
                done += 1
                continue
            yield item

    def _resolve_dict(self, digest: str) -> List[str]:
        """One-shot side-channel fetch for a by-ref dictionary. In-process
        deployments never get here (producer and consumer share the intern
        table); across processes, any upstream worker that shipped the ref
        has it interned, so try each distinct base once."""
        seen = set()
        for loc in self.locations:
            base = loc.split("/v1/")[0]
            if base in seen or not base.startswith("http"):
                continue
            seen.add(base)
            try:
                with urllib.request.urlopen(f"{base}/v1/dict/{digest}",
                                            timeout=30) as r:
                    return json.loads(r.read())
            except Exception:
                continue
        raise ExchangeFailure(
            f"dictionary {digest[:12]} unresolvable from any upstream",
            task_error=True)

    def decode(self, page, tracer=None) -> Batch:
        """One page as a batch on the device; `tracer` records its
        `page_decode` and `page_upload` (serde.deserialize_batch). A device
        batch handed in process needs neither (a broadcast's is shared by
        its consumers: no consumer program donates an input batch)."""
        if isinstance(page, Batch):
            _scan_metrics.record("exchange_handoff_batches", 1)
            return page
        _scan_metrics.record("exchange_page_batches", 1)
        return deserialize_batch(page, dict_resolver=self._resolve_dict,
                                 tracer=tracer)

    def batches(self) -> Iterator[Batch]:
        for page in self.pages():
            yield self.decode(page)

    def close(self):
        self.closed = True
