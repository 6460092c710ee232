"""Plan steps T a solve walks, on average over the run's plans, from the
plan statistics the record lists (``ExecPlan.stats()``'s ``n_steps``).
None where the record lists no plans."""


def read(rec):
    plans = rec.get("plans") or []
    if not plans:
        return None
    return sum(p["n_steps"] for p in plans) / len(plans)
