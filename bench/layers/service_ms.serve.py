"""Mean milliseconds a window request spends in the service after it
leaves the queue, from dispatch (its batch's pop) to done, from the
program's ``serve.request`` records (submit to done, less ``queue_s``)."""
import numpy as np

from bench import program_spans

program_spans.switch_on()


def read(rec):
    sec = program_spans.section(rec)
    if not sec or not sec["requests"]:
        return None
    return 1e3 * float(np.mean([service for _, service in sec["requests"]]))
