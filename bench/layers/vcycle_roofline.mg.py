"""Share of the roofline of HPCG's V-cycle: its least time, counted from
the hierarchy's matrices (``bench/mg_roofline.py``: the 14 triangular
solves and 6 SpMVs), over the device busy seconds of the traced window
divided by the V-cycles traced there (where the device record ends before
the window, the V-cycles in the recorded part, in proportion)."""


def read(rec):
    tr = rec.get("trace")
    if (not tr or not tr.get("vcycle_least_s") or not tr["busy_s"]
            or not tr.get("cycles")):
        return None
    return 100.0 * tr["vcycle_least_s"] / (tr["busy_s"] / tr["cycles"])
