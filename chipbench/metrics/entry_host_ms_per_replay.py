"""Entry point, host side: milliseconds of the traced replay in which no
device program ran.

Layer: ``core/device_simulate.simulate_trace`` outside the step program:
key staging (``_trace_lanes``), ``init_step_state``, dispatch, and the
readback of the registers and hits.  The replay's span on the harness's
clock less the time that the device's programs, the placed step program
and the ops cover in it, averaged over the traced chips.  The gaps between
the step program's own ops are the device's, not the entry's, and do not
count here.
"""


def reduce(trace, record):
    s0, s1 = trace["span"]
    idle = [(s1 - s0 - sum(max(0, min(s1, b) - max(s0, a))
                           for a, b in c["busy"])) / 1e6
            for c in trace["chips"].values() if c["step"]]
    return sum(idle) / len(idle) if idle else None
