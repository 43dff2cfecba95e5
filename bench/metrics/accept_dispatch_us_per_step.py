"""Device microseconds per cycle-loop step in the accept-and-dispatch stage
(``stage.accept_dispatch`` and ``stage.accept_dispatch_sched``): command
acceptance, the regulator, burst splitting into the beat-slot ring.  Same
steps as ``device_us_per_step``; see bench/stage_time.py."""
from bench.stage_time import stage_us_per_step


def read(run):
    return stage_us_per_step(run, "accept_dispatch")
