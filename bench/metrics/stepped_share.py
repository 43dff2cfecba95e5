"""Share of the simulated time the cycle loop stepped through.

``sum(effective - skipped) / sum(effective)`` over the window's points:
the rest is idle stretches the schedule pipeline's time skip jumped.
"""


def read(run):
    eff = sum(int(p["effective_cycles"]) for c in run.calls for p in c.points)
    skipped = sum(int(p["skipped_cycles"]) for c in run.calls
                  for p in c.points)
    return (eff - skipped) / eff if eff else None
