"""Share of the regulated ports' stepped cycles in which the token-bucket
regulator alone held the port's due command.

``sum(reg_held) / sum(regulated ports * stepped cycles)`` over the
window's points, with stepped cycles ``effective - skipped`` (a cycle the
time skip jumps holds nothing) and the regulated ports the best-effort
masters of the point's per-class summary (the class the scenario layer
puts at the regulated level).  ``None`` where the program reports no
``reg_held`` or no port is regulated.
"""


def read(run):
    held = port_cycles = 0
    for c in run.calls:
        for p, cls in zip(c.points, c.per_class):
            if "reg_held" not in p:
                return None
            ports = ((cls or {}).get("besteffort") or {}).get("masters", 0)
            stepped = int(p["effective_cycles"]) - int(p["skipped_cycles"])
            held += int(p["reg_held"])
            port_cycles += ports * stepped
    return held / port_cycles if port_cycles else None
