"""Pallas TPU kernel: sequential conservative-update (minimal increment) adds.

The paper's Add is order-dependent (later keys see earlier increments), so
the batch is walked key by key while the sketch stays VMEM-resident — one
HBM round-trip per *batch* instead of per *decision*.  Keys stream through
SMEM in blocks over a sequential grid; each probed word is read as its
``(8, 128)`` tile and written back as a masked tile store (the TPU has no
scalar VMEM stores).  This preserves the exact sequential semantics of the
host sketch (core/sketch.py) and of the jnp oracle (ref.py `add_ref`), which
the tests check bit-for-bit.

Input/output aliasing donates the counter and doorkeeper buffers, so the
update is in-place in HBM between batches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sketch_common import (DeviceSketchConfig, probe_index, dk_probe_index,
                            nibble_get, nibble_inc)
from .sketch_estimate import (KEY_BLOCK, block_keys, tile_view, untile,
                              pad_keys, word_select, read_word, vmem_params)


def _update_kernel(cfg: DeviceSketchConfig, nvalid_ref, lo_ref, hi_ref,
                   counters_in, dk_in, counters_out, dk_out):
    # aliased buffers: materialize input -> output once, then mutate out_refs
    @pl.when(pl.program_id(0) == 0)
    def _():
        counters_out[...] = counters_in[...]
        dk_out[...] = dk_in[...]

    def body(i, _):
        klo = lo_ref[i].astype(jnp.uint32)
        khi = hi_ref[i].astype(jnp.uint32)

        # ---- doorkeeper: membership test + insert (always) ----------------
        if cfg.dk_bits:
            present = jnp.int32(1)
            for p in range(cfg.dk_probes):
                bit = dk_probe_index(klo, khi, p, cfg.dk_bits)
                word = bit >> 5
                sel = word_select(word)
                tile = dk_out[word >> 10]
                w = read_word(tile, sel)
                present &= (w >> (bit & 31)) & 1
                dk_out[word >> 10] = jnp.where(
                    sel, w | (jnp.int32(1) << (bit & 31)), tile)
            gate = present == 1            # repeat visitor -> main table
        else:
            gate = True

        # ---- main table: minimal increment ---------------------------------
        probes = []
        for r in range(cfg.rows):
            idx = probe_index(klo, khi, r, cfg.width)
            word = idx >> 3
            sel = word_select(word)
            tile = counters_out[r, word >> 10]
            w = read_word(tile, sel)
            probes.append((word, idx & 7, sel, tile, w, nibble_get(w, idx & 7)))
        m = functools.reduce(jnp.minimum, [p[-1] for p in probes])
        bump = gate & (m < cfg.cap)
        for r, (word, nib, sel, tile, w, v) in enumerate(probes):
            @pl.when(bump & (v == m))
            def _():
                counters_out[r, word >> 10] = jnp.where(
                    sel, nibble_inc(w, nib), tile)
        return 0

    jax.lax.fori_loop(0, block_keys(nvalid_ref), body, 0)


def add_pallas(cfg: DeviceSketchConfig, state: dict, lo: jnp.ndarray,
               hi: jnp.ndarray, *, interpret: bool = False) -> dict:
    """Sequential batch add of the (lo, hi) keys, in order."""
    (b,) = lo.shape
    lo, hi = pad_keys(lo.astype(jnp.int32)), pad_keys(hi.astype(jnp.int32))
    counters = tile_view(state["counters"])
    dk = tile_view(state["doorkeeper"].reshape(-1))
    keys = pl.BlockSpec((KEY_BLOCK,), lambda k: (k,),
                        memory_space=pltpu.SMEM)
    resident = pl.BlockSpec(memory_space=pltpu.VMEM)
    counters, dk = pl.pallas_call(
        functools.partial(_update_kernel, cfg),
        out_shape=(jax.ShapeDtypeStruct(counters.shape, jnp.int32),
                   jax.ShapeDtypeStruct(dk.shape, jnp.int32)),
        grid=(lo.shape[0] // KEY_BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # batch size
                  keys, keys, resident, resident],
        out_specs=(resident, resident),
        input_output_aliases={3: 0, 4: 1},
        # the tables and their output copies
        compiler_params=vmem_params(counters, dk, counters, dk),
        interpret=interpret,
    )(jnp.asarray([b], jnp.int32), lo, hi, counters, dk)
    return {"counters": untile(counters, state["counters"].shape[-1]),
            "doorkeeper": untile(dk, state["doorkeeper"].size).reshape(
                state["doorkeeper"].shape),
            "size": state["size"] + b}
