"""How full the plans keep the scan's lanes: the occupied lane slots of
the run's plans over all their slots (T steps times k lanes each), from
the plan statistics the record lists (``ExecPlan.stats()``:
``row_slot_utilization`` is the occupied share of that plan's T·k slots;
a row split over accumulation lanes occupies one slot a lane). None where
the record lists no plans."""


def read(rec):
    plans = rec.get("plans") or []
    slots = [p["n_steps"] * p["k"] for p in plans]
    if not plans or not sum(slots):
        return None
    rows = sum(p["row_slot_utilization"] * s for p, s in zip(plans, slots))
    return rows / sum(slots)
