"""Device microseconds per cycle-loop step in the inter-slice router
(``stage.router_release``: hop latency and ingress credits).  Same steps as
``device_us_per_step``; see bench/stage_time.py."""
from bench.stage_time import stage_us_per_step


def read(run):
    return stage_us_per_step(run, "router_release")
