"""Share of the window in which no program ran on the device (profiler
trace: 1 - union of module intervals / window)."""


def read(o, peak):
    if o.summary is None:
        return None
    return 100.0 * o.summary.idle_share
