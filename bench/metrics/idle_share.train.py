"""Share of the traced window in which no operation ran, on the most idle
chip of the mesh: one minus the union of that chip's op intervals over the
window."""


def read(rec):
    if rec.trace is None:
        return None
    idle = rec.trace.idle_share_max()
    return None if idle is None else 100.0 * idle
