"""Device microseconds per cycle-loop step in retirement (``stage.retire``
and ``stage.retire_sched``: completion stamps, counters, the drain latch).
Same steps as ``device_us_per_step``; see bench/stage_time.py."""
from bench.stage_time import stage_us_per_step


def read(run):
    return stage_us_per_step(run, "retire")
