"""Share of the roofline of the V-cycle's SpMV program (the program's
``_spmv_ell``, ``jit__spmv_ell`` in the chip trace): the SpMVs' least
time, counted from the hierarchy's matrices (``bench/mg_roofline.py``),
over the device time of that program's whole runs in the traced window,
for as many V-cycles as those runs make. Nothing where no such program
ran."""


def read(rec):
    tr = rec.get("trace")
    spmv = tr.get("spmv") if tr else None
    if not spmv or not spmv["runs"] or not spmv["seconds"]:
        return None
    cycles = spmv["runs"] / spmv["per_cycle"]
    return 100.0 * tr["spmv_least_s"] * cycles / spmv["seconds"]
