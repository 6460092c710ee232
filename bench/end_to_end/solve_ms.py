"""Window wall time over the triangular solves completed in it; every call
ends in ``block_until_ready``. Closed-loop cells only."""


def read(rec):
    w = rec["window"]
    if rec["driver"] != "closed_loop" or not w.get("solves"):
        return None
    return 1e3 * w["wall_s"] / w["solves"]
