"""Set-up: process start to the first timed call (data generation,
inspector, bind, compile or cache load, warm-up), on the host clock."""


def read(rec):
    return rec["setup_s"]
