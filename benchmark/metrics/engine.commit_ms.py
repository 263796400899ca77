"""The commit of a launch (what ``engine.sample_commit`` brackets: every
row's cache commit, stream callback and retire check, and the expert
counts), per launch over the window: ``summary()``'s ``commit_time_s``
over ``launches`` (``c1`` less ``c0``).  A program without the counter
gives nothing to read."""


def read(ctx):
    c0, c1 = ctx["c0"], ctx["c1"]
    n = (c1.get("launches") or 0) - (c0.get("launches") or 0)
    if "commit_time_s" not in c1 or n <= 0:
        return None
    return 1e3 * (c1["commit_time_s"]
                  - (c0.get("commit_time_s") or 0.0)) / n
