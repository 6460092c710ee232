"""Host seconds the inspector spends scheduling in set-up: the program's
``inspector.schedule`` span, which covers a pinned strategy's scheduler,
or under ``strategy="auto"`` the DAG, its features and the scoring of
every candidate; over all the set-up's plans."""
from bench import program_spans

program_spans.switch_on()


def read(rec):
    return program_spans.total(rec, "setup", "inspector.schedule")
