"""Host milliseconds the service's worker spends on one batch outside the
device wait: the program's ``serve.batch.stack`` (stack and pad),
``serve.batch.dispatch`` (the call up to its return: the host permutation
and the transfer) and ``serve.batch.fulfil`` (the tickets and the
metrics), summed per batch and averaged over the batches the window's
requests rode."""
import numpy as np

from bench import program_spans

program_spans.switch_on()


def read(rec):
    sec = program_spans.section(rec)
    if not sec or not sec["batches"]:
        return None
    return 1e3 * float(np.mean(sec["batches"]))
