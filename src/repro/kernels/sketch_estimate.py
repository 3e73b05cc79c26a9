"""Pallas TPU kernel: batched TinyLFU frequency estimation.

The whole sketch (packed 4-bit counters + doorkeeper bitset) is pinned in
VMEM for the duration of a batch — the TPU analogue of the paper's "fits in
a single memory page".  Keys stream through SMEM in blocks; each key's
probes are hashed on the scalar unit and every probed word is read as one
``(8, 128)`` tile with a dynamic leading index, the word picked out of the
tile by a masked reduction.  Nothing is ever sized by the table width, so
the kernel compiles at any table that fits VMEM.

The same tile helpers serve the sequential conservative update
(sketch_update.py), which writes a probed word back as a masked tile store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sketch_common import (DeviceSketchConfig, probe_index, dk_probe_index,
                            nibble_get)

SUB, LANE = 8, 128
TILE = SUB * LANE          # int32 words per (8, 128) tile
KEY_BLOCK = 1024           # keys per grid step (SMEM-resident; XLA tiles
                           # a 1-D int32 array in 1024s)


def tile_view(words: jnp.ndarray) -> jnp.ndarray:
    """(..., n) int32 words -> (..., ceil(n / TILE), 8, 128) tiles, zero
    padded: the layout the kernels index by ``word >> 10``."""
    n = words.shape[-1]
    pad = (-n) % TILE
    if pad:
        words = jnp.pad(words, [(0, 0)] * (words.ndim - 1) + [(0, pad)])
    return words.reshape(words.shape[:-1] + (-1, SUB, LANE))


def untile(tiles: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`tile_view`: drop the padding, restore (..., n)."""
    return tiles.reshape(tiles.shape[:-3] + (-1,))[..., :n]


def pad_keys(x: jnp.ndarray) -> jnp.ndarray:
    """Pad a key-lane vector to a whole number of key blocks."""
    pad = (-x.shape[0]) % KEY_BLOCK
    return jnp.pad(x, (0, pad)) if pad else x


def word_select(word):
    """(8, 128) mask of word ``word``'s position inside its tile."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANE), 1)
    return sub * LANE + lane == (word & (TILE - 1))


def read_word(tile, sel):
    """The one selected int32 word of ``tile`` as a scalar."""
    return jnp.sum(jnp.where(sel, tile, 0))


def key_estimate(cfg: DeviceSketchConfig, counters_ref, dk_ref, klo, khi):
    """Paper §3.4.2 estimate of one key from VMEM-resident tiles."""
    est = jnp.int32(15)
    for r in range(cfg.rows):
        idx = probe_index(klo, khi, r, cfg.width)
        word = idx >> 3
        w = read_word(counters_ref[r, word >> 10], word_select(word))
        est = jnp.minimum(est, nibble_get(w, idx & 7))
    if cfg.dk_bits:
        ok = jnp.int32(1)
        for p in range(cfg.dk_probes):
            bit = dk_probe_index(klo, khi, p, cfg.dk_bits)
            word = bit >> 5
            w = read_word(dk_ref[word >> 10], word_select(word))
            ok &= (w >> (bit & 31)) & 1
        est = est + ok
    return est


def vmem_params(*arrays) -> pltpu.CompilerParams:
    """Sequential grid over key blocks, with a VMEM limit that covers the
    resident ``arrays``."""
    need = sum(a.size * a.dtype.itemsize for a in arrays)
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=max(32 << 20, need + (8 << 20)))


def block_keys(nvalid_ref) -> jnp.ndarray:
    """Valid keys in this grid step's block (the batch is padded)."""
    return jnp.clip(nvalid_ref[0] - pl.program_id(0) * KEY_BLOCK, 0,
                    KEY_BLOCK)


def _estimate_kernel(cfg: DeviceSketchConfig, nvalid_ref, lo_ref, hi_ref,
                     counters_ref, dk_ref, out_ref):
    def body(i, _):
        klo = lo_ref[i].astype(jnp.uint32)
        khi = hi_ref[i].astype(jnp.uint32)
        out_ref[i] = key_estimate(cfg, counters_ref, dk_ref, klo, khi)
        return 0

    jax.lax.fori_loop(0, block_keys(nvalid_ref), body, 0)


def estimate_pallas(cfg: DeviceSketchConfig, state: dict, lo: jnp.ndarray,
                    hi: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Batched estimate: (B,) int32, one per (lo, hi) key."""
    (b,) = lo.shape
    lo, hi = pad_keys(lo.astype(jnp.int32)), pad_keys(hi.astype(jnp.int32))
    counters = tile_view(state["counters"])
    dk = tile_view(state["doorkeeper"].reshape(-1))
    keys = pl.BlockSpec((KEY_BLOCK,), lambda k: (k,),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_estimate_kernel, cfg),
        out_shape=jax.ShapeDtypeStruct(lo.shape, jnp.int32),
        grid=(lo.shape[0] // KEY_BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # batch size
                  keys, keys,
                  pl.BlockSpec(memory_space=pltpu.VMEM),   # counter tiles
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # doorkeeper tiles
        out_specs=keys,
        compiler_params=vmem_params(counters, dk),
        interpret=interpret,
    )(jnp.asarray([b], jnp.int32), lo, hi, counters, dk)
    return out[:b]
