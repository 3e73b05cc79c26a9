"""Per-access step: device microseconds per access of the step program.

Layer: ``kernels/sketch_step.step_ref`` under
``core/device_simulate._jit_step``.  The traced replay runs the step
program once; its device interval (``chip["step"]``, from its first op in
the head session to its last in the tail) over the replay's accesses,
averaged over the traced chips.
"""


def reduce(trace, record):
    per = [(c["step"][1] - c["step"][0]) / 1e3 / record["accesses_per_replay"]
           for c in trace["chips"].values() if c["step"]]
    return sum(per) / len(per) if per else None
