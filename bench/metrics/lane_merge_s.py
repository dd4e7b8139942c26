"""Lane merge collectives: device seconds per job of the all-reduce ops
(the ``psum``/``pmin`` carry merges) inside the programs named
``lanes_super_step``, from the profiler trace, summed on each device and
averaged over the devices that ran them.  Only a trace that covers whole
jobs is read."""

import re

PROGRAM = "lanes_super_step"
# an op event is named by its HLO instruction, ``%name = shape opcode(...)``
ALL_REDUCE = re.compile(r" all-reduce(-start|-done)?\(")


def read(run):
    if run.trace is None or not run.edges_traced or not run.jobs_in_window:
        return None
    per_device = []
    for ops in run.trace.ops_in_window().values():
        sec = sum(e - s for name, s, e, prog in ops
                  if PROGRAM in prog and ALL_REDUCE.search(name)) * 1e-9
        if sec:
            per_device.append(sec)
    if not per_device:
        return None
    return sum(per_device) / len(per_device) / run.jobs_in_window
