"""Device microseconds per cycle-loop step that no stage scope claims: loop
plumbing, carry copies, the drain predicate, set-up and result reductions,
operations of other programs.  Same steps as ``device_us_per_step``; see
bench/stage_time.py."""
from bench.stage_time import UNSTAGED, stage_us_per_step


def read(run):
    return stage_us_per_step(run, UNSTAGED)
