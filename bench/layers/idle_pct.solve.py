"""Device idle share of the traced window in a closed-loop solve cell:
1 - (union of device op intervals) / (traced window)."""


def read(rec):
    tr = rec.get("trace")
    if rec["driver"] != "closed_loop" or not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
