"""Compile (or compile-cache load) seconds: the warm-up calls less one
steady call of the same programs, summed over the programs."""


def read(rec):
    return rec["compile_s"]
