"""Host milliseconds per traced call in the program's ``repro.fetch`` span
(waiting for the device, copying the results back) during which no
operation ran on the device."""
from bench.stage_time import host_phase_ms


def read(run):
    return host_phase_ms(run, "repro.fetch")
