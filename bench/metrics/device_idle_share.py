"""Device: the share of the traced window in which the device ran
nothing, ``1 - busy / window``, busy being the union of the intervals of
the ops and programs the profiler trace records on the device."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or run.busy_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace.window_s)
