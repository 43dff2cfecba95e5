"""Host milliseconds per call into the model that no device work covers.

For each traced call (``simulate`` or ``CompiledScenario.simulate`` /
``simulate_batch``): its span in the profiler's trace less the time within
it during which an operation ran on the device.  That is host preparation
(packing, padding, placing), dispatch, the fetch to NumPy and the host-side
summary.  Averaged over the traced calls.
"""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    spans = run.trace.host_spans.get("bench.call", [])
    if not spans:
        return None
    idle = [(t1 - t0) - run.trace.busy_within(t0, t1) for t0, t1 in spans]
    return sum(idle) / len(idle) / 1e6
