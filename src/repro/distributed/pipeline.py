"""Pipeline parallelism over a mesh axis via shard_map + collective_permute
(GPipe schedule) — the multi-pod mesh's 'pod' axis can act as a 2-deep
pipeline instead of pure DP (DESIGN.md §4).

The layer stack (L, ...) is split into S contiguous stages; a global batch is
split into M microbatches.  Every step t of the S+M-1 schedule, stage s
processes microbatch (t - s) if live, then activations ppermute to stage
s+1.  Bubble fraction = (S-1)/(S+M-1), amortized by M.

`pipeline_apply` is the forward executor (inference/eval and the building
block for interleaved training); equivalence vs the sequential stack is
checked in tests/test_pipeline.py on a host-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_axis: str, block_fn, stacked_params,
                   x: jnp.ndarray, n_micro: int):
    """Run ``x`` through the full stacked layer sequence, stages sharded over
    ``stage_axis``.

    block_fn(params_slice, h) -> h applies ONE layer.
    stacked_params: pytree with leading layer axis L (L % n_stages == 0).
    x: (B, ...) global batch (B % n_micro == 0).
    """
    n_stages = mesh.shape[stage_axis]
    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0
    B = x.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro

    def stage_body(params_local, x_all):
        # params_local: (L/S, ...) this stage's layers; x_all: full batch
        # (replicated over the stage axis — microbatches stream through)
        sid = jax.lax.axis_index(stage_axis)
        micros = x_all.reshape(n_micro, mb, *x_all.shape[1:])
        T = n_stages + n_micro - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def layers(h):
            def body(h, p):
                return block_fn(p, h), None
            h, _ = jax.lax.scan(body, h, params_local)
            return h

        def step(carry, t):
            inbuf, outs = carry
            # stage 0 injects microbatch t; others use what arrived
            m_idx = jnp.clip(t, 0, n_micro - 1)
            injected = jnp.where(sid == 0, 1, 0)
            h_in = jnp.where(injected, micros[m_idx], inbuf)
            live = (t - sid >= 0) & (t - sid < n_micro)
            h_out = jnp.where(live, layers(h_in), h_in)
            # last stage collects its finished microbatch
            done_idx = t - (n_stages - 1)
            is_done = (sid == n_stages - 1) & (done_idx >= 0) \
                & (done_idx < n_micro)
            outs = jax.lax.cond(
                is_done,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, h_out[None], jnp.clip(done_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # forward activations to the next stage
            nxt = jax.lax.ppermute(h_out, stage_axis, perm)
            return (nxt, outs), None

        inbuf0 = jnp.zeros((mb, *x_all.shape[1:]), x_all.dtype)
        outs0 = jnp.zeros((n_micro, mb, *x_all.shape[1:]), x_all.dtype)
        (_, outs), _ = jax.lax.scan(step, (inbuf0, outs0),
                                    jnp.arange(n_stages + n_micro - 1))
        # only the last stage holds real outputs; gather + select them
        outs = jax.lax.all_gather(outs, stage_axis)[n_stages - 1]
        return outs.reshape(B, *x_all.shape[1:])

    params_spec = jax.tree_util.tree_map(
        lambda a: P(stage_axis, *([None] * (a.ndim - 1))), stacked_params)
    fn = jax.shard_map(stage_body, mesh=mesh,
                       in_specs=(params_spec, P()), out_specs=P(),
                       check_vma=False)
    return fn(stacked_params, x)
