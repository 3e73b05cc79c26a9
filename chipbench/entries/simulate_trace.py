"""Entry: ``repro.core.device_simulate.simulate_trace``, one call per trace.

Each call replays one trace of the pool through a cold cache built from
the configuration's ``kwargs`` and waits until the device is done.  The
mix's ``warmup`` prefix is replayed and left out of the counted hits.
``readback`` gives what the reference is compared with: every access's hit
flag, the program's own count of hits past the warm-up, and the final
sketch words.
"""
from __future__ import annotations

import numpy as np


def make(kwargs: dict, mix: dict, program=None):
    """``call(keys) -> out``: one whole replay, finished on the device.
    ``program`` stands in for ``simulate_trace`` (tests plant faults)."""
    import jax
    if program is None:
        from repro.core.device_simulate import simulate_trace as program
    warmup = int(mix.get("warmup", 0))

    def call(keys):
        res, state, hits = program(keys, warmup=warmup, return_state=True,
                                   **kwargs)
        jax.block_until_ready((state, hits))
        return res, state, hits
    return call


def units(keys) -> int:
    """Accesses in one replay."""
    return int(keys.size)


def readback(out) -> dict:
    res, state, hits = out
    return {"hits": np.asarray(hits),
            "counted": int(res.hits),
            "counters": np.asarray(state["counters"]),
            "doorkeeper": np.asarray(state["doorkeeper"])}
