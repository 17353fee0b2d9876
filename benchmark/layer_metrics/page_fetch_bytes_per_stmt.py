"""scheduler + operators: bytes a statement's sinks copy from the device to
make its exchange pages (`items` of `page_fetch`: every plane of a batch,
whole, before the live mask drops the dead lanes; `serde.serialize_batch` as
`server/worker.py`'s sinks call it), all threads, mean per statement. Less
the pages' own bytes (`items` of `page_encode`), it is what dropping the
dead lanes and compressing take off. Repeats exactly for one text and one seed. `None`
for a statement that recorded no `page_fetch`: a program from before the
page path had phases."""

from benchmark import phase_summaries as ps


def per_statement(summary):
    found = [agg.get("items", 0) for _, name, agg in ps.phases(summary)
             if name == "page_fetch"]
    return float(sum(found)) if found else None


def read(run):
    return ps.mean(run, per_statement)
