"""Share of the traced window in which no operation ran on the device:
``1 - busy / window``, with busy the union of the device-operation
intervals (averaged over the chips)."""


def read(run):
    if run.trace is None or not run.trace.busy or run.trace.window_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
