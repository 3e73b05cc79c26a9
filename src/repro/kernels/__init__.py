"""Pallas TPU kernels for the TinyLFU sketch hot path + jnp oracles.

Layout (per the kernel deliverable spec):
  sketch_estimate.py / sketch_update.py — the serving sketch's estimate and
      sequential add as pl.pallas_call kernels (SMEM key blocks, VMEM tiles)
  sketch_step.py — fused W-TinyLFU simulation step: doorkeeper insert +
      conservative add + candidate/victim estimate + admission verdict +
      window/SLRU table update in ONE VMEM-resident launch per trace chunk
      (the engine behind core/device_simulate.py)
  ops.py — jit'd public wrappers (+ DeviceTinyLFU facade)
  ref.py — pure-jnp oracles, bit-exact ground truth for the kernels
"""
from .sketch_common import (DeviceSketchConfig, init_state, keys_to_lanes,
                            merge_words)
from .ops import estimate, add, reset, admit, make_config, DeviceTinyLFU
from .sketch_step import (StepSpec, make_step_params, init_step_state,
                          step_ref, step_pallas)
from .sketch_merge import merge_halve

__all__ = ["DeviceSketchConfig", "init_state", "keys_to_lanes", "estimate",
           "add", "reset", "admit", "make_config", "DeviceTinyLFU",
           "StepSpec", "make_step_params", "init_step_state", "step_ref",
           "step_pallas", "merge_words", "merge_halve"]
