"""Device microseconds per cycle-loop step in per-bank arbitration
(``stage.bank_arbitrate``, the arbiter backend included).  Same steps as
``device_us_per_step``; see bench/stage_time.py."""
from bench.stage_time import stage_us_per_step


def read(run):
    return stage_us_per_step(run, "bank_arbitrate")
