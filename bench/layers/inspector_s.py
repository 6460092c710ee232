"""Host seconds in the program's inspector: the benchmark's host clock
around its ``TriangularSolver.plan`` (or ``SolveService.register``) calls."""


def read(rec):
    return rec["inspector_s"]
