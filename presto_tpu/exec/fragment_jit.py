"""Whole-fragment device residency: hand a window of scan batches to ONE
compiled XLA program that stacks them and folds the breaker's merge step
over the stack.

The per-batch driver loop costs a host→device dispatch per operator per
batch — a host round trip each, while the chip does microseconds of work
(on the v5e an eager dispatch costs 0.25 ms and a fused step's call
0.53 ms: PERF_LEDGER, PR 25). This module removes the loop from the host:
consecutive same-structure batches are grouped into a "window", and the
breaker's own jitted stepping program stacks the window along a new
leading axis and iterates it with a `lax.scan` on-device. A fragment then
costs O(ceil(batches / window)) dispatches instead of O(batches ×
operators), and the host touches no array on the way: a window is a tuple
of references to the batches the scan yielded (the device split cache's
arrays), never a copy.

Pieces (mechanism only — eligibility gating and the program keys live in
exec/runtime.py, which owns the plan/breaker knowledge):

- ``batch_struct_key``: the stacking-compatibility key. Two batches stack
  iff their pytrees are structurally identical — same column names/types,
  same dictionary OBJECTS (Dictionary equality is identity, so one
  treedef match guarantees `_unify_batch_dicts` no-ops inside the traced
  scan body), same validity/limb presence, same leaf shapes and dtypes.
- ``iter_windows``: groups a batch stream into windows of at most `width`
  batches. A ragged tail is padded up to the next power of two by
  repeating the last real batch BY REFERENCE; the step gets `k` as a
  traced operand and kills slot i's live mask where i >= k (dead rows
  contribute nothing to a group merge or a TopN heap). The compiled
  window shapes stay bounded — {2, 4, ..., width} plus the per-batch
  single path — and a ragged window shares the full window's program.
- ``WindowSource``: the producer. A host thread pulls the (already
  decode-prefetched) scan stream, groups it into windows, and stages them
  in a depth-1 queue, so the pull of window k+1 overlaps the consumer's
  call of the fused step for window k. It makes no jax call. ``drain()``
  recovers every pulled-but-undispatched batch for the grace-spill path.
- ``scan_stepper`` / ``topn_stepper``: builders for the fused stepping
  functions runtime.py hands to `_node_jit` (one shared program per plan
  structure via exec/programs.py). The stack and the dead-tail mask are
  traced code inside them.

Everything here is kernel code for the analysis plane: the module is part
of the kernel linter's jit-rooted scope (analysis/kernel_lint.py).
"""

from __future__ import annotations

import functools
import queue
import threading
from typing import Callable, Iterable, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from presto_tpu.batch import Batch
from presto_tpu.obs import trace as _obs_trace


class Window:
    """A window of `k` real batches for one fused step. `batches` is a
    tuple of `width` references (`width` = k rounded up to a power of two,
    or the bucket width): the real batches in stream order, then the last
    real one repeated. They are the scan's own arrays — shared with the
    split cache, other statements and other threads — so a window is never
    donated and never copied on the host."""

    __slots__ = ("batches", "k", "width")

    def __init__(self, batches: Tuple[Batch, ...], k: int):
        self.batches = batches
        self.k = k
        self.width = len(batches)

    @property
    def operands(self):
        """`(batches, k)` as the fused steppers take them: `k` as an array
        operand (traced, so one program serves every k of a width, and its
        value stays out of the program's avals key)."""
        return self.batches, _live_count(self.k)


@functools.lru_cache(maxsize=None)
def _live_count(k: int) -> jax.Array:
    """`k` as a device-resident int32 scalar, made once a process for each
    value (there are at most `fragment_window` of them) at a fused step's
    call site: handing the step a host scalar instead costs a host→device
    transfer a window, 0.42 ms on the v5e (builder's chip run, PR 26).
    Uncommitted, so it follows the batches' device; never donated."""
    return jnp.asarray(k, dtype=jnp.int32)


WindowItem = Union[Batch, Window]


def batch_struct_key(b: Batch):
    """Hashable stacking-compatibility key: treedef (names, types, dict
    identities, optional-plane presence) + per-leaf (shape, dtype)."""
    leaves, treedef = jax.tree_util.tree_flatten(b)
    return treedef, tuple((tuple(l.shape), str(l.dtype)) for l in leaves)


def window_device_bytes(w: Window) -> int:
    """Device footprint of a window's stacked form, the fused step's
    temporary (for spill accounting): `width` same-shape batches."""
    return w.width * sum(l.size * l.dtype.itemsize
                         for l in jax.tree_util.tree_leaves(w.batches[0]))


def _pow2_at_least(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def iter_windows(stream: Iterable[Batch], width: int,
                 bucket: bool = False) -> Iterator[WindowItem]:
    """Group CONSECUTIVE same-structure batches into windows of at most
    `width`; a batch whose structure differs from its predecessors
    flushes the pending group first (order is always preserved). Lone
    batches pass through as they are — padding a single to width would
    spend width× the compute to save zero dispatches. ``bucket``
    (shape_bucketing=pow2) pads every MULTI-batch flush to the full
    window width, collapsing the partial-window pow2 ladder to one
    window shape per structure."""
    # host generator, never traced: width is a plain Python int
    bw = _pow2_at_least(int(width)) if bucket else 0  # lint: allow(host-sync)
    pending: List[Batch] = []
    key = None
    for b in stream:
        k = batch_struct_key(b)
        if pending and k != key:
            yield _flush(pending, bw)
            pending = []
        key = k
        pending.append(b)
        if len(pending) >= width:
            yield _flush(pending, bw)
            pending = []
    if pending:
        yield _flush(pending, bw)


def _flush(pending: List[Batch], bucket_width: int = 0) -> WindowItem:
    k = len(pending)
    if k == 1:
        return pending[0]
    # host-side padding decision: bucket_width is a plain Python int
    width = max(_pow2_at_least(k), int(bucket_width))  # lint: allow(host-sync)
    with _obs_trace.current().phase("window_stack", items=k):
        # references only: the stack and the dead tail are the step's
        w = Window(tuple(pending) + (pending[-1],) * (width - k), k)
    from presto_tpu.obs import devprof as _devprof

    if _devprof.active():
        # device-residency accounting: the fused path's staging
        # high-water is the step's stacked window, not a single batch
        _devprof.note_staging(window_device_bytes(w))
    return w


_SENTINEL = object()


class WindowSource:
    """Async window producer: a host thread pulls the scan stream (itself
    fed by the decode-prefetch producer), compares each batch's structure
    key with its predecessor's, collects references into windows and
    stages them in a depth-1 queue — one window with the consumer, one
    staged, so the pull of the next window overlaps the fused step's call.
    The thread makes no jax call and moves no data: stacking and dead-tail
    masking happen inside the consumer's compiled step.

    ``drain()`` stops the producer and returns every batch it pulled from
    the stream but the consumer never received (staged windows' real
    batches, plus the partial pending group) — the grace-overflow path
    hands these to the spill partitioner so no input is lost when the
    consumer abandons the window loop mid-stream."""

    def __init__(self, stream: Iterable[Batch], width: int,
                 bucket: bool = False,
                 on_window: Optional[Callable[[int, int], None]] = None):
        self._stream = iter(stream)
        # window-boundary telemetry hook (obs/inflight publish): called
        # (k, width) from the producer thread after each staged flush —
        # host-side counts only, never a device sync. None = no-op.
        self._on_window = on_window
        # host-side producer config, not traced code (the module-wide
        # kernel scope is for the stepper builders below)
        self._width = max(2, int(width))  # lint: allow(host-sync)
        # shape_bucketing=pow2: partial windows pad to the full width so
        # the fused stepper sees exactly one window shape per structure
        self._bucket_w = _pow2_at_least(self._width) if bucket else 0
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._exc: Optional[BaseException] = None
        self._pending: List[Batch] = []
        # the producer thread records into its creator's tracer
        self._tracer = _obs_trace.current()
        self._thread = threading.Thread(
            target=self._produce, name="fragment-window-producer", daemon=True)
        self._thread.start()

    def _produce(self):
        with _obs_trace.use(self._tracer):
            self._produce_windows()

    def _produce_windows(self):
        pending = self._pending
        key = None
        bw = self._bucket_w
        try:
            for b in self._stream:
                k = batch_struct_key(b)
                if pending and k != key:
                    if not self._put(_flush(pending, bw)):
                        return
                    del pending[:]
                key = k
                pending.append(b)
                if len(pending) >= self._width:
                    if not self._put(_flush(pending, bw)):
                        return
                    del pending[:]
                if self._stop.is_set():
                    return
            if pending and self._put(_flush(pending, bw)):
                del pending[:]
        except BaseException as e:  # propagated to the consumer
            self._exc = e
        finally:
            self._put(_SENTINEL, force=True)

    def _put(self, item, force: bool = False) -> bool:
        if item is not _SENTINEL and self._on_window is not None:
            k, width = (item.k, item.width) if isinstance(item, Window) \
                else (1, 1)
            try:
                self._on_window(k, width)
            except Exception:
                # telemetry must never kill the producer thread
                pass
        with self._tracer.phase("window_queue_full", wait=True):
            while True:
                stopped = self._stop.is_set()
                if stopped and not force:
                    return False
                try:
                    self._q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    if stopped and force:
                        # nobody will consume after a stop — drop the
                        # sentinel rather than spin against a full queue
                        # under join()
                        return False

    def __iter__(self) -> Iterator[WindowItem]:
        while True:
            with self._tracer.phase("window_wait", wait=True):
                item = self._q.get()
            if item is _SENTINEL:
                if self._exc is not None:
                    exc, self._exc = self._exc, None
                    raise exc
                return
            yield item

    def close(self):
        self._stop.set()
        self._thread.join(timeout=30.0)

    def drain(self) -> List[Batch]:
        """Stop the producer and recover its pulled-but-undelivered batches
        in stream order: staged queue items first, then the partial group."""
        self._stop.set()
        self._thread.join(timeout=30.0)
        rest: List[Batch] = []
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is _SENTINEL:
                continue
            if isinstance(item, Window):
                rest.extend(item.batches[:item.k])
            else:
                rest.append(item)
        rest.extend(self._pending)
        del self._pending[:]
        return rest


# ---------------------------------------------------------------------------
# fused stepping-function builders (runtime.py jits these via _node_jit)


def _first_and_rest(batches: Tuple[Batch, ...], k) -> Tuple[Batch, Batch]:
    """Traced: a window's first batch as it is (peeled, so never copied)
    and slots 1.. stacked along a new leading axis for `lax.scan` — the
    aux (names/types/dicts) is shared, so every slice sees the SAME
    dictionary objects. Slot i is dead where i >= k: a ragged tail's
    padding repeats a real batch and must contribute nothing. `k` is a
    traced int32 scalar, at least 1."""
    rest = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches[1:])
    real = jnp.arange(1, len(batches), dtype=jnp.int32) < k
    return batches[0], rest.with_live(rest.live & real[:, None])


def scan_stepper(merge_step: Callable, first: bool) -> Callable:
    """Fused aggregate fragment step: stack a window's `width` batches (`k`
    of them real) and fold `merge_step` (acc, batch, cap) -> (acc,
    n_groups) over them via `lax.scan`, returning the window's final
    accumulator and its MAX group count (the one scalar the host confirms
    per window instead of per batch). The first batch is peeled outside
    the scan so the carry is seeded with the step's own output structure —
    `merge_step` is a structural fixed point (its output feeds its input)
    only from the second application on.

    `first=True` builds the no-incoming-accumulator variant (window 0)."""

    def fold(acc0, batches, k, cap: int):
        first_b, rest = _first_and_rest(batches, k)
        acc, ng = merge_step(acc0, first_b, cap)

        def body(carry, b):
            a, mx = carry
            out, n = merge_step(a, b, cap)
            return (out, jnp.maximum(mx, n)), None

        (acc, ng), _ = jax.lax.scan(body, (acc, ng), rest)
        return acc, ng

    if first:
        def fragment_step0(batches, k, cap: int):
            return fold(None, batches, k, cap)

        return fragment_step0

    def fragment_step(acc, batches, k, cap: int):
        return fold(acc, batches, k, cap)

    return fragment_step


def topn_stepper(topn_step: Callable, first: bool) -> Callable:
    """Fused TopN fragment step: stack a window and fold `topn_step` (acc,
    batch) -> acc over it. TopN never overflows (the heap capacity is the
    query's LIMIT), so the carry is just the accumulator."""

    def fold(acc0, batches, k):
        first_b, rest = _first_and_rest(batches, k)
        acc = topn_step(acc0, first_b)

        def body(a, b):
            return topn_step(a, b), None

        acc, _ = jax.lax.scan(body, acc, rest)
        return acc

    if first:
        def fragment_topn0(batches, k):
            return fold(None, batches, k)

        return fragment_topn0

    def fragment_topn(acc, batches, k):
        return fold(acc, batches, k)

    return fragment_topn
