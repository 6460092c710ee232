"""Lower-triangular Erdős–Rényi matrix of arXiv 2503.05408 §6.2.4.

The pattern comes from the configuration's ``pattern_seed``, the values
from the run's seed. One call is one lower solve.
"""
from bench.matrices import erdos_renyi_lower


def operators(spec: dict, value_seed) -> list:
    a = erdos_renyi_lower(int(spec["n"]), float(spec["p"]),
                          pattern_seed=int(spec["pattern_seed"]),
                          value_seed=value_seed)
    return [(a, True)]
