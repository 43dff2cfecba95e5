"""Simulated fabric cycles completed per wall second.

Every real design point of the window counts its own effective cycles: its
drain cycle, or its horizon where it did not drain.  The nominal horizon
never counts, and padding lanes count nothing.  The time is the whole
window on the host clock, from the start of the first call to the end of
the last, so host work, dispatch and device work all count.
"""


def read(run):
    cycles = sum(int(p["effective_cycles"]) for c in run.calls
                 for p in c.points)
    return cycles / run.window_s
