"""Jitted public wrappers around the sketch kernels.

* ``use_pallas`` selects the Pallas kernels (compiled on a TPU, interpret
  mode elsewhere) or the pure-jnp oracle (ref.py) for ``add`` and
  ``estimate``; ``admit`` is two estimates and a compare on either path,
* ``reset`` is always the XLA oracle: one elementwise pass that XLA already
  runs at memory speed on every platform, so it has no kernel,
* composes `add` with the automatic reset (paper §3.3: reset once the sample
  counter reaches W).

`DeviceTinyLFU` is the stateful convenience facade used by the serving
scheduler (serve/prefix_cache.py).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .sketch_common import DeviceSketchConfig, init_state, keys_to_lanes
from .sketch_estimate import estimate_pallas
from .sketch_update import add_pallas


def _interpret() -> bool:
    """Mosaic compiles for the TPU only; elsewhere the kernels interpret."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# functional ops (jit-friendly; cfg static)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 4))
def estimate(cfg: DeviceSketchConfig, state: dict, lo: jnp.ndarray,
             hi: jnp.ndarray, use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return ref.estimate_ref(cfg, state, lo, hi)
    return estimate_pallas(cfg, state, lo, hi, interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(0, 4))
def add(cfg: DeviceSketchConfig, state: dict, lo: jnp.ndarray,
        hi: jnp.ndarray, use_pallas: bool = True) -> dict:
    """Batch add + automatic reset when the sample counter crosses W."""
    if use_pallas:
        new = add_pallas(cfg, state, lo, hi, interpret=_interpret())
    else:
        new = ref.add_ref(cfg, state, lo, hi)
    if cfg.sample_size:
        new = jax.lax.cond(new["size"] >= cfg.sample_size,
                           functools.partial(ref.reset_ref, cfg),
                           lambda s: s, new)
    return new


@functools.partial(jax.jit, static_argnums=(0,))
def reset(cfg: DeviceSketchConfig, state: dict) -> dict:
    return ref.reset_ref(cfg, state)


@functools.partial(jax.jit, static_argnums=(0, 6))
def admit(cfg: DeviceSketchConfig, state: dict, cand_lo, cand_hi,
          victim_lo, victim_hi, use_pallas: bool = True) -> jnp.ndarray:
    """(B,) bool: admit candidate i over victim i (paper Fig 1)."""
    if not use_pallas:
        return ref.admission_ref(cfg, state, cand_lo, cand_hi,
                                 victim_lo, victim_hi)
    b = cand_lo.shape[0]
    est = estimate_pallas(cfg, state, jnp.concatenate([cand_lo, victim_lo]),
                          jnp.concatenate([cand_hi, victim_hi]),
                          interpret=_interpret())
    return est[:b] > est[b:]


# ---------------------------------------------------------------------------
# stateful facade
# ---------------------------------------------------------------------------

def _pow2ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def make_config(num_blocks: int, sample_factor: int = 8,
                counters_per_item: float = 2.0, rows: int = 4,
                dk_bits_per_item: float = 4.0) -> DeviceSketchConfig:
    """Same sizing rule as core.sketch.default_sketch (≈1.5 B/sample elem)."""
    sample = sample_factor * num_blocks
    width = _pow2ceil(max(8, counters_per_item * sample / rows))
    width = max(width, 8)
    return DeviceSketchConfig(
        width=width, rows=rows, cap=min(15, max(1, sample_factor - 1)),
        dk_bits=max(32, _pow2ceil(sample * dk_bits_per_item)),
        sample_size=sample)


class DeviceTinyLFU:
    """Stateful TinyLFU over device arrays (serving-side admission).

    Keys are uint64 (block hashes); batches are converted to 32-bit lanes on
    the way in.  All methods are O(batch) with the sketch resident on device.
    """

    def __init__(self, num_blocks: int, sample_factor: int = 8,
                 use_pallas: bool = True, **kw):
        self.cfg = make_config(num_blocks, sample_factor=sample_factor, **kw)
        self.state = init_state(self.cfg)
        self.use_pallas = use_pallas

    def record(self, keys: np.ndarray) -> None:
        if len(keys) == 0:
            return
        lo, hi = keys_to_lanes(keys)
        self.state = add(self.cfg, self.state, lo, hi, self.use_pallas)

    def estimate(self, keys: np.ndarray) -> np.ndarray:
        if len(keys) == 0:
            return np.zeros(0, np.int32)
        lo, hi = keys_to_lanes(keys)
        return np.asarray(estimate(self.cfg, self.state, lo, hi,
                                   self.use_pallas))

    def admit(self, cands: np.ndarray, victims: np.ndarray) -> np.ndarray:
        if len(cands) == 0:
            return np.zeros(0, bool)
        clo, chi = keys_to_lanes(cands)
        vlo, vhi = keys_to_lanes(victims)
        return np.asarray(admit(self.cfg, self.state, clo, chi, vlo, vhi,
                                self.use_pallas))
