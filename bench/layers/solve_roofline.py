"""Share of the roofline of the solve program (the scan, or the Pallas
``sptrsv_tile`` kernel with its relayouts): the least time of one solve,
counted from its matrix (``bench/roofline.py``), over the device time of
everything one solve dispatches. That is the mean device time of the
program's whole runs in the traced window, over the program's share of the
chip's busy time there, so that gathers and relayouts run as programs of
their own count too. Only where nothing but solves runs on the device in
the window."""


def read(rec):
    tr = rec.get("trace")
    if (not tr or not tr.get("solve_only") or not tr["program"]
            or not tr["program"]["runs"]):
        return None
    p = tr["program"]
    per_solve = p["seconds"] / p["runs"] / p["share"]
    return 100.0 * tr["solve_least_s"] / per_solve
