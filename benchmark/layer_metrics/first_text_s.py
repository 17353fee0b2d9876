"""compile plane: what a statement costs whose text differs from a warm one
only in its literals - the mean wall, in set-up, of the first statement of
every distinct text after the first (the first also pays the upload of the
columns). Today each such text mints programs again (PERF.md); with literals
as program arguments this falls to `statement_s`. Nothing to read in a cell
that sends one text."""


def read(run):
    walls = run["setup_counters"]["first_walls_s"][1:]
    return sum(walls) / len(walls) if walls else None
