"""Device time per plan step of the scan executor: the device seconds of
the solve program's whole runs in the traced window over the plan steps
they walked (T per run). Only where every solve runs on the scan backend
and nothing else runs on the device in the window."""


def read(rec):
    tr = rec.get("trace")
    if (not tr or not tr.get("solve_only") or rec["backends"] != ["scan"]
            or not tr["program"] or not tr["program"]["runs"]):
        return None
    p = tr["program"]
    return 1e6 * p["seconds"] / (p["runs"] * tr["solve_steps"])
