#!/usr/bin/env python3
"""Smoke run of the engine and the serving admission path on a TPU.

    python3 chip_smoke.py              # one chip: phases a-d
    python3 chip_smoke.py --chips 4    # four chips: the sharded mesh run only

One process drives every phase through the public entry points and checks
each result bit for bit against an independent run:

  a  key-value cache, YCSB workload C shape: 2^19 Zipf(0.99) reads over
     2^23 keys, the first 2^17 warmup, through ``simulate_trace`` at
     C=2^20, assoc=8, sample_factor=8.  The hit vector must equal the same
     program run on the host CPU backend.  (The trace is cut from 2^22: the
     per-access scan costs ~0.2 ms on a v5e at this capacity, so the main
     table does not fill here; phase b evicts.)
  b  a fleet of 64 tenant caches (``streams=64``, C=4096, assoc=8) over
     ``tenant_lanes_trace`` with 2^16 accesses per lane, so the larger
     tenants evict and every lane crosses the §3.3 reset; every lane's hits
     and the final state must equal the CPU run.
  c  phase a's configuration with ``shards=4`` (the in-program
     ``merge_halve`` fold runs); hits and final sketch words against CPU.
  d  serving admission: ``DeviceTinyLFU(num_blocks=131072)`` records 2^21
     Zipf block hashes in batches of 1024 (crossing the §3.3 reset), then
     answers ``estimate`` and ``admit`` for 1024 pairs; estimates, verdicts
     and final sketch words must equal the ``use_pallas=False`` XLA path on
     the same chip.

``--chips 4`` runs phase c's configuration on a 2^16-access trace over
``make_shard_mesh(4, require=4)`` with ``mesh_exchange="chunk"`` and
compares it with the single-device sharded run on the first chip.

Each phase prints smoke figures (compile and run seconds, the compiled
program's ``memory_analysis()``, the device's peak bytes in use): they show
that the phase ran, and are not benchmark metrics.  The script exits
non-zero, before any phase, when JAX finds no TPU; the last line of a
successful run is the JSON object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

TRACE_LEN, WARMUP = 1 << 19, 1 << 17          # phases a and c
KEYSPACE, CAPACITY = 1 << 23, 1 << 20
LANES, LANE_CAP, LANE_LEN = 64, 4096, 1 << 16
NUM_BLOCKS, RECORDS, BATCH = 131072, 1 << 21, 1024
MESH_TRACE_LEN, MESH_WARMUP = 1 << 16, 1 << 14  # --chips 4

# XLA (and Mosaic) compile time; tracing nests and is left in run_s
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event, secs, **_):
    if event == _COMPILE_EVENT:
        _compile_s[0] += secs


class Phase:
    """Times one phase and prints its smoke figures on exit."""

    def __init__(self, name: str, device):
        self.name, self.device = name, device
        self.memory = {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        print(f"[smoke {self.name}] start", flush=True)
        return self

    def program(self, compiled):
        """Record one compiled program's ``memory_analysis()``."""
        ma = compiled.memory_analysis()
        self.memory = {k: getattr(ma, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        compile_s = _compile_s[0] - self.c0
        stats = self.device.memory_stats() or {}
        print(f"[smoke {self.name}] smoke figures: "
              + json.dumps({"compile_s": compile_s,
                            "run_s": wall - compile_s,
                            "memory_analysis": self.memory,
                            "peak_bytes_in_use":
                                stats.get("peak_bytes_in_use")}),
              flush=True)
        return False


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def same(a, b, what: str) -> None:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    check(a.shape == b.shape, f"{what}: shapes {a.shape} vs {b.shape}")
    diff = int((a != b).sum())
    check(diff == 0, f"{what}: {diff} of {a.size} elements differ")
    print(f"  {what}: {a.size} elements bit-identical", flush=True)


def engine_program(trace, capacity: int, warmup: int = 0, **cfg_kw):
    """The compiled program ``simulate_trace`` dispatches for this
    configuration (already in jit's cache after the run)."""
    import jax.numpy as jnp
    from repro.core.device_simulate import (DeviceWTinyLFU, _jit_step,
                                            _mesh_runner, _sharded_runner,
                                            _chunk_lanes, _trace_lanes)
    from repro.kernels.sketch_step import init_step_state
    cfg = DeviceWTinyLFU(capacity, **cfg_kw)
    spec, params = cfg.spec(), cfg.params(warmup=warmup)
    state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
    lo, hi = _trace_lanes(trace)
    if cfg.shards == 1:
        return _jit_step.lower(spec, params, state, lo, hi).compile()
    E = cfg.merge_epoch
    ne = lo.shape[-1] // E
    if cfg.mesh is not None:
        nfull = ne * E
        return _mesh_runner(spec, cfg.mesh, False).lower(
            params, state, lo[:nfull].reshape(ne, E),
            hi[:nfull].reshape(ne, E), lo[nfull:], hi[nfull:]).compile()
    return _sharded_runner(spec, "jit", False).lower(
        params, state, _chunk_lanes(lo, ne, E), _chunk_lanes(hi, ne, E),
        jnp.full((ne,), E, jnp.int32)).compile()


def engine_phase(name, device, trace, capacity, *, warmup=0,
                 compare_state=False, **cfg_kw):
    """Run one ``simulate_trace`` configuration on ``device`` and check its
    hits (and, if asked, its final state words) against the CPU backend."""
    import jax
    import numpy as np
    from repro.core.device_simulate import simulate_trace
    n = trace.shape[-1]
    with Phase(name, device) as ph:
        with jax.default_device(device):
            res, state, hits = simulate_trace(
                trace, capacity, warmup=warmup, return_state=True, **cfg_kw)
            hits = np.asarray(hits)
            ph.program(engine_program(trace, capacity, warmup, **cfg_kw))
    check(hits.shape == trace.shape, f"{name}: hit shape {hits.shape}")
    check(set(np.unique(hits).tolist()) <= {0, 1}, f"{name}: hits not 0/1")
    check(int(hits[..., warmup:].sum()) == res.hits,
          f"{name}: hit vector disagrees with the hit register")
    check(0.0 < res.hit_ratio < 1.0, f"{name}: hit ratio {res.hit_ratio}")
    print(f"  {name}: {res.accesses} counted accesses, hit ratio "
          f"{res.hit_ratio!r} on {device.platform}", flush=True)

    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        _, cstate, chits = simulate_trace(
            trace, capacity, warmup=warmup, return_state=True, **cfg_kw)
        chits = np.asarray(chits)
    print(f"  {name}: CPU reference over {n} accesses per lane took "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    same(hits, chits, f"{name}: hit vector vs CPU")
    if compare_state:
        for k in ("counters", "doorkeeper", "regs"):
            same(state[k], cstate[k], f"{name}: final {k} vs CPU")
    return res, state, hits


def serving_phase(device):
    """Phase d: the serving admission path, Pallas kernels vs XLA."""
    import jax
    import numpy as np
    from repro.core.hashing import splitmix64
    from repro.kernels import ops
    from repro.kernels.ops import DeviceTinyLFU
    from repro.kernels.sketch_common import keys_to_lanes
    from repro.traces import zipf_trace
    ids = zipf_trace(RECORDS + 2 * BATCH, n_items=4 * NUM_BLOCKS,
                     alpha=0.99, seed=3)
    blocks = splitmix64(ids.astype(np.uint64))
    stream, cands, victims = (blocks[:RECORDS], blocks[RECORDS:-BATCH],
                              blocks[-BATCH:])
    out = {}
    with Phase("d", device) as ph:
        with jax.default_device(device):
            for use_pallas in (True, False):
                t = DeviceTinyLFU(NUM_BLOCKS, use_pallas=use_pallas)
                t0 = time.perf_counter()
                for i in range(0, RECORDS, BATCH):
                    t.record(stream[i:i + BATCH])
                est = t.estimate(cands)
                verdict = t.admit(cands, victims)
                jax.block_until_ready(t.state)
                print(f"  d: use_pallas={use_pallas}: {RECORDS} records + "
                      f"{BATCH} estimates + {BATCH} verdicts in "
                      f"{time.perf_counter() - t0:.1f} s", flush=True)
                out[use_pallas] = (t, est, verdict)
            t = out[True][0]
            lo, hi = keys_to_lanes(stream[:BATCH])
            ph.program(ops.add.lower(t.cfg, t.state, lo, hi, True).compile())
    t, est, verdict = out[True]
    cfg = t.cfg
    check(cfg.sample_size == 8 * NUM_BLOCKS, "d: sample size")
    check(int(t.state["size"]) < cfg.sample_size,
          "d: the sample counter never crossed the §3.3 reset")
    check(est.shape == (BATCH,) and 0 <= est.min()
          and est.max() <= cfg.cap + 1 and est.max() > 0,
          f"d: estimates out of range [{est.min()}, {est.max()}]")
    same(est, out[False][1], "d: estimates vs XLA")
    same(verdict, out[False][2], "d: admit verdicts vs XLA")
    for k in ("counters", "doorkeeper", "size"):
        same(t.state[k], out[False][0].state[k], f"d: final {k} vs XLA")
    print(f"  d: admit rate {float(verdict.mean())!r}, size after resets "
          f"{int(t.state['size'])}", flush=True)


def one_chip(device) -> None:
    import numpy as np
    from repro.traces import tenant_lanes_trace, zipf_trace
    tr = zipf_trace(TRACE_LEN, n_items=KEYSPACE, alpha=0.99, seed=0)
    kw = dict(assoc=8, sample_factor=8)
    engine_phase("a", device, tr, CAPACITY, warmup=WARMUP, **kw)
    lanes = tenant_lanes_trace(LANES, LANE_LEN, seed=1)
    res, _, hits = engine_phase("b", device, lanes, LANE_CAP, assoc=8,
                                streams=LANES, compare_state=True)
    same(np.asarray(res.extra["lane_hits"]), hits.sum(axis=1),
         "b: per-lane hit registers vs hit vectors")
    engine_phase("c", device, tr, CAPACITY, warmup=WARMUP,
                 compare_state=True, shards=4, **kw)
    serving_phase(device)


def four_chips(devices) -> None:
    import jax
    import numpy as np
    from repro.core.device_simulate import simulate_trace
    from repro.distributed.mesh import make_shard_mesh
    from repro.traces import zipf_trace
    tr = zipf_trace(MESH_TRACE_LEN, n_items=KEYSPACE, alpha=0.99, seed=0)
    kw = dict(assoc=8, sample_factor=8, shards=4, warmup=MESH_WARMUP)
    mesh = make_shard_mesh(4, devices=devices, require=4)
    print(f"  mesh: {mesh}", flush=True)
    with Phase("mesh", devices[0]) as ph:
        res, mstate, mhits = simulate_trace(
            tr, CAPACITY, mesh=mesh, mesh_exchange="chunk",
            return_state=True, **kw)
        mhits = np.asarray(mhits)
        ph.program(engine_program(tr, CAPACITY, mesh=mesh, **kw))
    for k, v in mstate.items():
        print(f"  mesh state {k}: shape {v.shape} sharding {v.sharding} "
              f"on {len(v.sharding.device_set)} devices", flush=True)
    print(f"  mesh: hit ratio {res.hit_ratio!r}", flush=True)
    with Phase("single", devices[0]) as ph:
        with jax.default_device(devices[0]):
            _, sstate, shits = simulate_trace(tr, CAPACITY,
                                              return_state=True, **kw)
            shits = np.asarray(shits)
            ph.program(engine_program(tr, CAPACITY, **kw))
    same(mhits, shits, "mesh vs single-device hit vector")
    for k in ("counters", "doorkeeper", "regs"):
        same(mstate[k], sstate[k], f"mesh vs single-device final {k}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    # the reference runs need the host CPU backend next to the TPU
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax
    from repro.compile_cache import use_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devices})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"devices: {devices}", flush=True)
    if args.chips == 4:
        four_chips(devices[:4])
    else:
        one_chip(devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
