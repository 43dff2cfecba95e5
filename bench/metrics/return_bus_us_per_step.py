"""Device microseconds per cycle-loop step on the return bus
(``stage.return_bus``).  Same steps as ``device_us_per_step``; see
bench/stage_time.py."""
from bench.stage_time import stage_us_per_step


def read(run):
    return stage_us_per_step(run, "return_bus")
