"""Exchanges between two tasks of one worker hand device batches, not pages.

A consumer task claims, when it is created, the partitions of the upstream
buffers that live on its own worker (server/worker.py); their producer then
enqueues the device batch itself, in the form a decoded page would take
(serde.handoff_batch), and the consumer reads the buffer in process
(server/exchange.py `_BufferPuller`). Exchanges to another worker and the
coordinator's result stream stay pages, as does a spooling buffer. The
catalog and the configuration are `test_grace_agg.py`'s, so the grace
aggregation's programs are shared with it.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import serde
from presto_tpu.batch import Batch, Column, round_up_capacity
from presto_tpu.dictionary import Dictionary
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.memory import batch_device_bytes
from presto_tpu.obs import trace as obs_trace
from presto_tpu.plan.fragmenter import OUT_BROADCAST
from presto_tpu.scan import metrics as scan_metrics
from presto_tpu.server.buffers import OutputBuffer
from presto_tpu.server.coordinator import DistributedRunner
from presto_tpu.server.exchange import ExchangeClient
from presto_tpu.server.worker import TaskManager, TaskUpdate
from presto_tpu.types import BIGINT, VARCHAR

from test_grace_agg import cat  # noqa: F401  (the module's catalog)

CONFIG = ExecConfig(batch_rows=1 << 12, agg_capacity=1 << 8,
                    agg_cap_ceiling=1 << 9, spill_partitions=4)

# a join, an aggregation of 9,000 groups past agg_cap_ceiling (grace), and
# a window over its result
SQL = ("select a.g, a.c, a.sx, b.mx, "
       "rank() over (partition by b.mx order by a.sx desc, a.g) as rk "
       "from (select g, g % 97 as k, count(*) as c, sum(x) as sx "
       "from t group by g) a "
       "join (select g % 97 as k, max(s) as mx from t group by g % 97) b "
       "on a.k = b.k")


def _exchange_counts():
    snap = scan_metrics.snapshot()
    return snap["exchange_handoff_batches"], snap["exchange_page_batches"]


def _served(cat, n_workers):
    """The answer on an in-process cluster of `n_workers`, its statement's
    phases and the exchange counters' growth over it."""
    before = _exchange_counts()
    with DistributedRunner(cat, n_workers=n_workers, config=CONFIG) as dr:
        df = dr.run(SQL)
    after = _exchange_counts()
    phases = obs_trace.summaries()[-1]["phases"]
    return (df.sort_values("g", ignore_index=True), phases,
            (after[0] - before[0], after[1] - before[1]))


@pytest.fixture(scope="module")
def served(cat):  # noqa: F811
    return {n: _served(cat, n) for n in (1, 2)}


def test_one_worker_answers_as_two_workers_do(served):
    one, two = served[1][0], served[2][0]
    assert len(one) > 8_000
    assert one.equals(two)


def test_one_worker_hands_every_task_exchange_off(served):
    """Only the root fragment's sink makes pages on one worker: every page
    a task fetched is one the coordinator decodes, and no task decodes."""
    _, phases, (handed, pages) = served[1]
    task = phases["task"]
    assert handed > 0 and task["page_handoff"]["n"] == handed
    assert "page_decode" not in task and "page_upload" not in task
    root = phases["coordinator"]["page_decode"]
    assert task["page_fetch"]["n"] == task["page_encode"]["n"] == root["n"]
    assert pages == root["n"]


def test_two_workers_cross_by_pages(served):
    """Across workers the exchanges stay pages, decoded by tasks; the
    tasks' exchanges within each worker are handed off."""
    _, phases, (handed, pages) = served[2]
    task = phases["task"]
    root = phases["coordinator"]["page_decode"]["n"]
    assert task["page_decode"]["n"] > 0
    assert pages == task["page_decode"]["n"] + root
    assert handed > 0 and task["page_handoff"]["n"] > 0


# -- the handed batch is what a decoded page would be -----------------------


def _sparse_batch(live_rows, capacity=1 << 17, dict_values=None):
    rng = np.random.default_rng(7)
    live = np.zeros(capacity, bool)
    live[np.sort(rng.choice(capacity, live_rows, replace=False))] = True
    values = rng.integers(-1 << 40, 1 << 40, capacity)
    valid = rng.random(capacity) < 0.9
    d = Dictionary(np.asarray(dict_values or [f"v{i:03d}" for i in range(200)],
                              dtype=object))
    codes = rng.integers(0, len(d.values), capacity).astype(np.int32)
    b = Batch(["k", "s"], [BIGINT, VARCHAR],
              [Column(jnp.asarray(values), jnp.asarray(valid)),
               Column(jnp.asarray(codes), None)],
              jnp.asarray(live), {"s": d})
    return b, int(live.sum())


def _planes(b):
    return [np.asarray(p) for p in serde._flat(b)]


@pytest.mark.parametrize("live_rows,capacity", [
    (366, 1 << 17),     # a date_dim build batch
    (1, 1 << 12),
    (90, 100),          # a capacity that is no bucket: padded up to 128
])
def test_a_sparse_batch_arrives_compacted_as_its_page_would(live_rows,
                                                            capacity):
    b, n = _sparse_batch(live_rows, capacity)
    handed = serde.handoff_batch(b, n)
    decoded = serde.deserialize_batch(serde.serialize_batch(b))
    assert handed.capacity == decoded.capacity == round_up_capacity(n)
    for got, want in zip(_planes(handed), _planes(decoded)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    # the live rows, in their order
    keep = np.asarray(b.live)
    np.testing.assert_array_equal(
        np.asarray(handed.columns[0].values)[:n],
        np.asarray(b.columns[0].values)[keep])


def test_a_handed_batch_carries_the_dictionaries_a_decode_returns():
    for values in (None, ["x", "y", "z"]):     # by reference, inlined
        b, n = _sparse_batch(1000, capacity=1 << 12, dict_values=values)
        handed = serde.handoff_batch(b, n)
        decoded = serde.deserialize_batch(
            serde.serialize_batch(b, dict_refs=True))
        assert handed.dicts["s"] is decoded.dicts["s"]
        assert set(handed.dicts) == set(decoded.dicts) == {"s"}


def test_a_handed_batch_keeps_its_radix_tag():
    b, n = _sparse_batch(3000, capacity=1 << 13)
    tag = (5, 16, ("k",))
    handed = serde.handoff_batch(b, n, radix=tag)
    decoded = serde.deserialize_batch(serde.serialize_batch(b, radix=tag))
    assert isinstance(handed, serde.TaggedBatch)
    assert handed.radix == decoded.radix == tag
    for got, want in zip(_planes(handed), _planes(decoded)):
        np.testing.assert_array_equal(got, want)


def test_a_batch_at_its_bucket_goes_as_it_is():
    b, n = _sparse_batch(3000, capacity=1 << 12)
    handed = serde.handoff_batch(b, n)
    assert handed.live is b.live
    assert all(h.values is c.values for h, c in zip(handed.columns,
                                                    b.columns))


# -- delivery: tokens, acks, aborts, broadcast ------------------------------


def _claimed_buffer(n_partitions=1, broadcast=False):
    buf = OutputBuffer(n_partitions, broadcast=broadcast)
    for p in range(n_partitions):
        assert buf.claim(p)
    return buf


def test_a_reget_of_an_unacked_token_returns_the_same_batches():
    buf = _claimed_buffer()
    batches = [serde.handoff_batch(*_sparse_batch(n, capacity=1 << 12))
               for n in (100, 2000)]
    for hb in batches:
        assert buf.hands_off(0)
        buf.enqueue(0, hb)
    first, nxt, _ = buf.get(0, 0, max_wait_s=0, in_process=True)
    again, nxt2, _ = buf.get(0, 0, max_wait_s=0, in_process=True)
    assert nxt == nxt2 == 2
    assert all(a is b is c for a, b, c in zip(first, again, batches))
    assert buf.buffered_bytes() == sum(map(batch_device_bytes, batches))


def test_ack_releases_the_device_batches():
    buf = _claimed_buffer()
    for n in (100, 2000):
        buf.enqueue(0, serde.handoff_batch(*_sparse_batch(n, 1 << 12)))
    buf.ack(0, 1)
    rest, _, _ = buf.get(0, 1, max_wait_s=0, in_process=True)
    assert buf.buffered_bytes() == batch_device_bytes(rest[0])
    buf.ack(0, 2)
    assert buf.buffered_bytes() == 0


def test_abort_releases_the_device_batches():
    buf = _claimed_buffer(2, broadcast=True)
    hb = serde.handoff_batch(*_sparse_batch(100, 1 << 12))
    buf.enqueue(None, hb)
    assert buf.buffered_bytes() == batch_device_bytes(hb)   # shared, once
    buf.abort(0)
    assert buf.buffered_bytes() == batch_device_bytes(hb)
    buf.abort(1)
    assert buf.buffered_bytes() == 0


def test_a_failed_producer_reaches_the_consumer_as_a_task_error():
    from presto_tpu.server.exchange import ExchangeFailure

    buf = _claimed_buffer()
    buf.fail("RuntimeError: boom")
    client = ExchangeClient(["up"], local={"up": (buf, 0)})
    with pytest.raises(ExchangeFailure, match="boom") as e:
        list(client.batches())
    assert e.value.task_error


def _tasks(cat, sql, config):  # noqa: F811
    """A task manager and `sql`'s fragments: its leaf's and its root's."""
    with DistributedRunner(cat, n_workers=1, config=config) as dr:
        dplan = dr.coordinator.plan_distributed(sql)
    tm = TaskManager(cat)
    tm.url = "http://127.0.0.1:1"          # stands for the worker's own URL
    (leaf,) = [fid for fid in dplan.fragments if fid != dplan.root_fid]
    return tm, leaf, dplan.fragments[leaf], dplan.fragments[dplan.root_fid]


def _post(tm, tid, fragment, n_out, upstreams, spool=False):
    """Create a task as a POST from the coordinator would: the update goes
    through its JSON codec, so that every task has plan nodes of its own."""
    from presto_tpu.plan.codec import (task_update_from_json,
                                       task_update_to_json)
    from presto_tpu.server.coordinator import _config_dict

    update = TaskUpdate(fragment, 0, 1, n_out, upstreams,
                        _config_dict(CONFIG), spool=spool)
    tm.update_task(tid, task_update_from_json(task_update_to_json(update)))
    return tm.get(tid)


def _wait(task):
    task.thread.join(120)
    assert task.state == "finished", task.error


def _answer(task):
    """A root task's answer, read as the coordinator reads its pages."""
    pages, _, done = task.buffer.get(0, 0, max_wait_s=0)
    assert done and all(isinstance(p, bytes) for p in pages)
    return [serde.deserialize_batch(p).to_pandas() for p in pages]


@pytest.mark.parametrize("sql", [
    "select sum(x) as s, max(x) as m, count(*) as c from t",   # donated acc
    "select g, x from t order by x desc, g limit 5",         # donated heap
])
def test_a_broadcast_serves_two_consumers_after_either_ran(cat, sql):  # noqa
    """One batch stream broadcast to two consumer tasks of one worker: the
    second reads it intact after the first's programs (a global
    aggregate's and a TopN's donate their accumulators) have run."""
    tm, leaf, src, root = _tasks(cat, sql, CONFIG)
    producer = _post(tm, "q.0.0", dataclasses.replace(
        src, output_partitioning=OUT_BROADCAST), 2, {})
    url = tm.url + "/v1/task/q.0.0/results/{}"
    want = LocalRunner(cat, CONFIG).run(sql)
    for i in range(2):
        consumer = _post(tm, f"q.1.{i}", root, 1, {leaf: [url.format(i)]})
        _wait(consumer)
        (got,) = _answer(consumer)
        assert got.equals(want), (i, got, want)
    _wait(producer)
    assert producer.buffer.buffered_bytes() == 0


def test_a_spooling_buffer_still_writes_pages(cat):  # noqa: F811
    """A phased schedule's build-phase task spools: no consumer may claim
    its buffer, and its sink makes pages."""
    sql = "select count(*) as c, sum(x) as s from t"
    tm, _, src, _ = _tasks(cat, sql, CONFIG)
    producer = _post(tm, "q.0.0", src, 1, {}, spool=True)
    _wait(producer)
    assert not producer.buffer.claim(0)
    assert not producer.buffer.hands_off(0)
    items, _, done = producer.buffer.get(0, 0, max_wait_s=0)
    assert done and items and all(isinstance(p, bytes) for p in items)


def test_a_phased_schedule_answers_with_pages_from_its_build_phase(cat):  # noqa
    """On one worker under execution_policy=phased, the join's build
    phase crosses by pages and the rest is handed off."""
    before = _exchange_counts()
    cfg = dataclasses.replace(CONFIG, execution_policy="phased")
    with DistributedRunner(cat, n_workers=1, config=cfg) as dr:
        df = dr.run(SQL)
    handed, pages = (a - b for a, b in zip(_exchange_counts(), before))
    task = obs_trace.summaries()[-1]["phases"]["task"]
    assert task["page_decode"]["n"] > 0 and handed > 0
    assert pages > task["page_decode"]["n"]
    with DistributedRunner(cat, n_workers=1, config=CONFIG) as dr:
        want = dr.run(SQL)
    assert df.sort_values("g", ignore_index=True).equals(
        want.sort_values("g", ignore_index=True))


def test_many_consumers_take_every_broadcast_batch_once_in_order():
    """A broadcast buffer under back-pressure, read in process by more
    consumers than cores while the interpreter switches threads often:
    each sees every batch once, in order, and the buffer ends empty."""
    import sys
    import threading

    consumers, n_batches = 12, 60
    live = jnp.ones(128, bool)
    vals = Column(jnp.arange(128), None)
    batches = [Batch(["k"], [BIGINT], [vals], live) for _ in range(n_batches)]
    size = batch_device_bytes(batches[0])
    buf = OutputBuffer(consumers, broadcast=True, max_buffered_bytes=3 * size)
    for p in range(consumers):
        assert buf.claim(p)
    seen = [[] for _ in range(consumers)]

    def consume(p):
        client = ExchangeClient(["up"], local={"up": (buf, p)})
        seen[p] = [id(b) for b in client.pages()]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=consume, args=(p,))
                   for p in range(consumers)]
        for t in threads:
            t.start()
        for b in batches:
            buf.enqueue(None, b)
        buf.set_no_more_pages()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert all(s == [id(b) for b in batches] for s in seen)
    assert buf.buffered_bytes() == 0 and buf.is_finished()
