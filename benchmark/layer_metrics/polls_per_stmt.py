"""protocol: nextUri GETs per completed statement."""


def read(run):
    done = run["completed"]
    return sum(s["polls"] for s in done) / len(done) if done else None
