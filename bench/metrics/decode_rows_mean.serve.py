"""Mean number of decode rows per engine step in the window (the
benchmark's own record of ``engine.active()`` before each step)."""


def read(rec):
    if not rec.steps:
        return None
    return sum(rec.steps) / len(rec.steps)
