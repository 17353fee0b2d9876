"""protocol: mean seconds from the POST to its response, per statement."""


def read(run):
    posts = [t1 - t0 for name, _, t0, t1 in run["spans"] if name == "post"]
    return sum(posts) / len(posts) if posts else None
