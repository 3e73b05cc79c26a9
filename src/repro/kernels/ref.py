"""Pure-jnp oracles for every Pallas sketch kernel.

These are the semantic ground truth: the kernels in sketch_update.py and
sketch_estimate.py must match them bit-for-bit (tests/test_kernels.py sweeps
shapes & dtypes).  ``reset_ref`` is the only reset: it has no kernel.
They are also directly usable — `jax.jit`-able, differentiable-free integer
code — wherever interpret-mode Pallas would be slower (CPU serving path).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .sketch_common import (DeviceSketchConfig, probe_index, dk_probe_index,
                            nibble_get, nibble_inc, halve_words)


# ---------------------------------------------------------------------------
# estimate
# ---------------------------------------------------------------------------

def _dk_contains(cfg: DeviceSketchConfig, dk: jnp.ndarray, lo, hi):
    """(B,) bool: all doorkeeper probe bits set."""
    flat = dk.reshape(-1)
    ok = jnp.ones(lo.shape, jnp.bool_)
    for p in range(cfg.dk_probes):
        bit = dk_probe_index(lo, hi, p, cfg.dk_bits)
        word = flat[bit >> 5]
        ok &= ((word >> (bit & 31)) & 1).astype(jnp.bool_)
    return ok


def _table_estimate(cfg: DeviceSketchConfig, counters: jnp.ndarray, lo, hi):
    """(B,) int32 min over rows of the 4-bit counters."""
    est = jnp.full(lo.shape, 15, jnp.int32)
    for r in range(cfg.rows):
        idx = probe_index(lo, hi, r, cfg.width)
        word = counters[r, idx >> 3]
        est = jnp.minimum(est, nibble_get(word, idx & 7))
    return est


def estimate_ref(cfg: DeviceSketchConfig, state: dict, lo: jnp.ndarray,
                 hi: jnp.ndarray) -> jnp.ndarray:
    """Paper §3.4.2 estimate: main-table min + 1 if the doorkeeper knows you."""
    est = _table_estimate(cfg, state["counters"], lo, hi)
    if cfg.dk_bits:
        est = est + _dk_contains(cfg, state["doorkeeper"], lo, hi).astype(jnp.int32)
    return est


# ---------------------------------------------------------------------------
# add (conservative update, sequential over the batch)
# ---------------------------------------------------------------------------

def add_ref(cfg: DeviceSketchConfig, state: dict, lo: jnp.ndarray,
            hi: jnp.ndarray) -> dict:
    """Sequential minimal-increment adds; later batch elements observe earlier
    updates (same order semantics as the host sketch and the Pallas kernel).
    Does NOT trigger reset — compose via ops.add_and_maybe_reset."""

    def one(carry, key):
        counters, dk = carry
        klo, khi = key

        def main_add(counters):
            idx = []
            vals = []
            for r in range(cfg.rows):
                i = probe_index(klo, khi, r, cfg.width)
                idx.append(i)
                vals.append(nibble_get(counters[r, i >> 3], i & 7))
            vals = jnp.stack(vals)
            m = vals.min()

            def bump(counters):
                new = counters
                for r in range(cfg.rows):
                    i = idx[r]
                    word = new[r, i >> 3]
                    word = jnp.where(vals[r] == m, nibble_inc(word, i & 7), word)
                    new = new.at[r, i >> 3].set(word)
                return new

            return jax.lax.cond(m < cfg.cap, bump, lambda c: c, counters)

        if cfg.dk_bits:
            flat = dk.reshape(-1)
            present = jnp.bool_(True)
            new_flat = flat
            for p in range(cfg.dk_probes):
                bit = dk_probe_index(klo, khi, p, cfg.dk_bits)
                word = new_flat[bit >> 5]
                present &= ((word >> (bit & 31)) & 1).astype(jnp.bool_)
                new_flat = new_flat.at[bit >> 5].set(word | (jnp.int32(1) << (bit & 31)))
            # repeat visitor -> main table; first-timer -> doorkeeper only
            counters = jax.lax.cond(present, main_add, lambda c: c, counters)
            dk = new_flat.reshape(dk.shape)
        else:
            counters = main_add(counters)
        return (counters, dk), None

    (counters, dk), _ = jax.lax.scan(
        one, (state["counters"], state["doorkeeper"]),
        (lo.astype(jnp.uint32), hi.astype(jnp.uint32)))
    return {"counters": counters, "doorkeeper": dk,
            "size": state["size"] + lo.shape[0]}


# ---------------------------------------------------------------------------
# reset
# ---------------------------------------------------------------------------

def reset_ref(cfg: DeviceSketchConfig, state: dict) -> dict:
    return {
        "counters": halve_words(state["counters"]),
        "doorkeeper": jnp.zeros_like(state["doorkeeper"]),
        "size": state["size"] // 2,
    }


# ---------------------------------------------------------------------------
# fused admission (paper Fig 1 decision, batched)
# ---------------------------------------------------------------------------

def admission_ref(cfg: DeviceSketchConfig, state: dict,
                  cand_lo, cand_hi, victim_lo, victim_hi) -> jnp.ndarray:
    """(B,) bool: admit candidate i over victim i (strictly greater freq)."""
    ce = estimate_ref(cfg, state, cand_lo, cand_hi)
    ve = estimate_ref(cfg, state, victim_lo, victim_hi)
    return ce > ve
