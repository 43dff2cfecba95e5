"""Host milliseconds per traced call in the program's ``repro.scenario``
span (``Scenario.compile()``: region placement, the masters' traffic
generators, packing the trace) during which no operation ran on the
device.  ``None`` where the program marks no such span."""
from bench.stage_time import host_phase_ms


def read(run):
    return host_phase_ms(run, "repro.scenario")
