"""Device milliseconds a V-cycle waits on the host: over the traced
window's calls (``bench.solve`` spans, one V-cycle each), the mean time
inside a call in which the chip runs no program (the driver's
``trace.cycle.idle_s``): from the call's start to its first program,
between programs where the host's dispatch fell behind the chip (the
coarse levels' short programs), and from the last program to the call's
return. The host's enqueue span (``mg.vcycle``) is no reading of host
cost: on a TPU v5e at 104^3 it lasts about two thirds of a 0.8 s
V-cycle, so the enqueue waits on the device."""


def read(rec):
    tr = rec.get("trace")
    idle = tr.get("cycle", {}).get("idle_s") if tr else None
    if idle is None:
        return None
    return 1e3 * idle
