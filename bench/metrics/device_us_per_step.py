"""Device microseconds per cycle-loop step.

Device busy time in the traced window (averaged over the chips), over the
cycle steps the loop executed there: per call its largest stepped count
(effective less skipped cycles), since every vmapped lane of a call steps
until the slowest drains.
"""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    steps = sum(int(c.stepped().max()) for c in run.traced_calls)
    return 1e6 * run.trace.busy_s / steps if steps else None
