"""Share of bank grants that went to a beat the arbiter's aging had
promoted above its master's own QoS level: ``sum(aged_grants) /
sum(slice_beats)`` over the window's points (every grant is one beat
served by a slice).  ``None`` where the program reports no
``aged_grants`` or nothing was granted."""


def read(run):
    aged = grants = 0
    for c in run.calls:
        for p in c.points:
            if "aged_grants" not in p:
                return None
            aged += int(p["aged_grants"])
            grants += int(sum(int(b) for b in p["slice_beats"]))
    return aged / grants if grants else None
