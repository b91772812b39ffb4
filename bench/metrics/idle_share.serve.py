"""Share of the traced window in which no operation ran on the chip: one
minus the union of the device's op intervals over the window."""


def read(rec):
    if rec.trace is None:
        return None
    idle = rec.trace.idle_share_max()
    return None if idle is None else 100.0 * idle
