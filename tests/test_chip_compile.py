"""The main path compiled for a described TPU v5e chip, with no chip present.

The TPU compiler refuses what interpret-mode Pallas and XLA-CPU accept: a
block not aligned to the (8, 128) tiling, scalar stores to VMEM, a kernel
or program that does not fit the chip's memory.  These tests compile the
engine's step programs and the serving kernels at deployment sizes for one
v5e chip, about two seconds each.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo_cost import _split_computations
from repro.analysis.program_lint import (_find_whiles, _max_out_elems,
                                         _reachable)
from repro.core.device_simulate import (DeviceWTinyLFU, _jit_step,
                                        _sharded_runner)
from repro.kernels import ops
from repro.kernels.sketch_step import _RECORD_BLOCK, init_step_state

TRACE = 1 << 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: what is
    compiled for it cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _engine_args(sharding, cfg: DeviceWTinyLFU, trace_shape):
    spec = cfg.spec()
    state = jax.eval_shape(
        lambda: init_step_state(spec, cfg.window_cap, cfg.main_cap))
    keys = jax.ShapeDtypeStruct(trace_shape, jnp.int32)
    return spec, _on(sharding, (cfg.params(), state, keys, keys))


@pytest.mark.parametrize("cfg, trace_shape", [
    (DeviceWTinyLFU(1 << 20, assoc=8), (TRACE,)),
    (DeviceWTinyLFU(4096, assoc=8, streams=64), (64, TRACE)),
], ids=["assoc8-C2^20", "streams64-C4096"])
def test_engine_step_compiles(one_chip, cfg, trace_shape):
    spec, (params, state, lo, hi) = _engine_args(one_chip, cfg, trace_shape)
    compiled = _jit_step.lower(spec, params, state, lo, hi).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


@pytest.mark.parametrize("trace, budget", [
    (TRACE, 1 << 30),
    # 32M accesses: the scalar columns alone take ~1.75 GB; a record
    # buffer as long as the trace would add 16 GB at 512 B an access
    (1 << 25, 2 << 30),
], ids=["T2^16", "T2^25"])
def test_kv64k_step_reads_its_record_as_one_row(one_chip, trace, budget):
    """The ``kv-64k`` cell's step (C=65536, assoc=8, streams=1): each access
    reads its packed window record as a row slice of its block's ``(K,
    wcols)`` records, K = ``_RECORD_BLOCK``.  The window phase builds no
    record from scalars (no ``concatenate`` under its scope), nothing
    block-sized is copied inside the access scan, and the program's temp
    stays within ``budget`` however long the trace."""
    cfg = DeviceWTinyLFU(65536, assoc=8, sample_factor=8, window_frac=0.01)
    spec, args = _engine_args(one_chip, cfg, (trace,))
    compiled = _jit_step.lower(spec, *args).compile()
    comps, _ = _split_computations(compiled.as_text())
    (body,) = [b for _, _, trips, b in _find_whiles(comps)
               if trips == _RECORD_BLOCK]
    ops = [(comps[c], op) for c in _reachable(comps, [body])
           for op in comps[c].ops.values()]
    assert not [op.name for _, op in ops if op.kind == "concatenate"
                and "/window/" in op.line]
    reads = [op.out_shapes for comp, op in ops if op.kind == "dynamic-slice"
             and comp.ops[op.operands[0]].out_shapes
             == [("s32", (_RECORD_BLOCK, spec.wcols))]]
    assert reads and all(r == [("s32", (1, spec.wcols))] for r in reads)
    assert not [op.name for _, op in ops
                if op.kind == "copy" and _max_out_elems(op) >= _RECORD_BLOCK]
    assert compiled.memory_analysis().temp_size_in_bytes < budget


def test_sharded_epoch_runner_compiles(one_chip):
    """shards=4: the epoch scan with the in-program merge_halve fold."""
    cfg = DeviceWTinyLFU(1 << 20, assoc=8, shards=4)
    E = cfg.merge_epoch
    spec, (params, state, los, his) = _engine_args(
        one_chip, cfg, (TRACE // E, E))
    nvalid = _on(one_chip, jax.ShapeDtypeStruct((TRACE // E,), jnp.int32))
    compiled = _sharded_runner(spec, "jit", False).lower(
        params, state, los, his, nvalid).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


@pytest.fixture
def serving(one_chip, monkeypatch):
    """The serving sketch at num_blocks=131072 and a batch of 1024 keys, with
    the ops steered to compiled (not interpret-mode) kernels, as on a TPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = ops.make_config(131072)
    state = {"counters": jax.ShapeDtypeStruct(
                 (cfg.rows, cfg.words_per_row), jnp.int32),
             "doorkeeper": jax.ShapeDtypeStruct((1, cfg.dk_words), jnp.int32),
             "size": jax.ShapeDtypeStruct((), jnp.int32)}
    keys = jax.ShapeDtypeStruct((1024,), jnp.uint32)
    return cfg, _on(one_chip, state), _on(one_chip, keys)


@pytest.mark.parametrize("op, n_keys, kernel", [
    ("add", 2, True), ("estimate", 2, True), ("admit", 4, True),
    ("reset", 0, False)])
def test_serving_op_compiles(serving, op, n_keys, kernel):
    """Each ``ops`` entry point compiles for the chip: add, estimate and
    admit as Mosaic kernels, reset as plain XLA."""
    cfg, state, keys = serving
    fn = getattr(ops, op).__wrapped__           # a fresh trace, not jit's
    use_pallas = (True,) if kernel else ()
    compiled = jax.jit(lambda s, *k: fn(cfg, s, *k, *use_pallas)).lower(
        state, *[keys] * n_keys).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
