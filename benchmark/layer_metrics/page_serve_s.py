"""scheduler + operators: seconds a statement's workers' request threads spend
answering a consumer's pull with pages: the response put together and
written to the socket, the long poll for them left out (`busy_s` of
`page_serve`, role `http`: `server/worker.py`, the `/results` handler), mean
per statement. `None` for a statement that recorded no `page_serve`: a
program from before the page path had phases."""

from benchmark import join_phases, phase_summaries as ps


def read(run):
    return ps.mean(run, join_phases.total("busy_s", names=("page_serve",)))
