"""Host seconds of the backend bind in set-up: the program's
``backend.bind`` span (the elastic certificate, the row permutations' and
the plan tensors' device puts), over all the set-up's plans."""
from bench import program_spans

program_spans.switch_on()


def read(rec):
    return program_spans.total(rec, "setup", "backend.bind")
