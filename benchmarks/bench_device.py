"""Host-vs-device simulation engine throughput (ours; ROADMAP north star).

Four measurements on the same golden Zipf trace:

1. **trace engine, exact semantics** — `run_trace(WTinyLFU)` (pure-Python
   per-access loop) vs `device_simulate.simulate_trace` (whole trace as one
   `lax.scan` program; `backend="pallas"` additionally exercises the fused
   VMEM-resident chunk kernel).  Both simulate the identical policy; hit
   ratios must agree to ±0.005 (the golden regression tests pin this).
2. **matrix throughput** — a (sizes × window fractions) Cartesian grid:
   host = Python loop per configuration, device = `simulate_sweep` (one
   compiled program reused across the grid).
3. **fused admission decision throughput** — the paper's Fig 1 hot path
   (record + candidate/victim estimate + verdict) on the same keys: host
   `FrequencySketch`/`TinyLFUAdmission` per-key loop vs the batched jnp twin
   of the fused kernel (`kernels.ops.add`/`ops.admit`).  This is the path the
   serving scheduler drives every tick, and where the batched device engine
   is expected to clear 10x even on CPU; the sequential trace engines above
   are reported as honest engine-vs-engine numbers for the current backend
   (CPU jit / interpret-mode Pallas stand-ins for the TPU deployment).
4. **capacity scaling** — the flat exact engine's per-access argmin is
   O(capacity); the set-associative tables (`assoc=8`) are O(ways).  Both
   engines run the golden Zipf trace at growing C; the set path must stay
   near-flat from C=512 to C=65536 and clear >= 5x the flat engine at
   C >= 8192 (ISSUE 2 acceptance).
5. **adaptive overhead** — the runtime hill-climbed window (ISSUE 3) adds
   per-access quota masks and an O(slots log) epoch rebalance; measured as
   adaptive-vs-static set-assoc throughput at C=8192.
6. **sharded sketch** (ISSUE 4) — ``shards=4`` splits the sketch into
   shard-local delta writes + global reads with an epoch-boundary
   merge_halve fold; measured as sharded-vs-unsharded set-assoc throughput
   at C=8192 plus the same 512->65536 flatness ratio with sharding enabled
   (the fold is amortized and the per-access delta path must stay
   capacity-free).
7. **multi-stream batched engine** (ISSUE 8) — ``StepSpec.streams=B``
   advances B independent tenant caches in one vmapped scan; measured as
   aggregate acc/s at B in {1, 16, 64} on the frozen small-tenant geometry
   (C=16 per tenant — the thousands-of-tenants regime the lane axis
   exists for, where per-op dispatch dominates the unbatched step).  The
   B=64 aggregate must clear >= 8x the single-stream rate (ISSUE 8
   acceptance; gate warns < 8, fails < 3).
8. **policy panel** (ISSUE 9) — the device-resident competitor policies
   (``policy="s3fifo" | "arc" | "lfu"``) run the golden Zipf trace in the
   same set-associative geometry as W-TinyLFU (C=8192, assoc=8); because
   all four share the fused per-access scan body, a competitor running
   > 2x slower than the default policy flags a shape break in its branch
   (gate arm 8 warns, never fails — hit ratios are pinned by the
   exactness tier in ``tests/test_policy_panel.py``, not here; ARC's
   ~4.5x ghost-Bloom maintenance cost is a known, documented exception).

See docs/BENCHMARKS.md for the snapshot fields and the CI gate arms.

All wall times are best-of-N to sidestep noisy-neighbour jitter; JSON rows
record every measurement, and a compact perf snapshot is written to
``BENCH_device.json`` at the repo root.  ``benchmarks/check_bench.py`` turns
the snapshot into a CI regression gate (see its docstring for the noise
model).  ``assoc_flatness_512_to_65536`` is ``acc/s at C=65536 divided by
acc/s at C=512`` — ~1.0 when the per-access cost is capacity-free, < 0.9
when something reintroduced O(capacity) work (gate direction; note PR 2's
snapshot recorded the inverse ratio).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import WTinyLFU, run_trace
from repro.core.sketch import default_sketch
from repro.core.tinylfu import TinyLFUAdmission
from repro.traces import zipf_trace, tenant_lanes_trace
from .common import save

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _machine_fingerprint() -> str:
    """CPU model + core count: throughput numbers are only comparable
    between snapshots taken on the same class of machine (check_bench.py
    skips the absolute-throughput gate when fingerprints differ)."""
    model = "unknown"
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{model} x{os.cpu_count()}"


# the mesh measurement runs on two FORCED HOST (CPU) devices in a child
# process — the device count can only be forced before jax initializes.  The
# child pins itself to the CPU platform, so it never competes with the parent
# for an accelerator, and its numbers are labelled as CPU numbers.
_MESH_BENCH_SCRIPT = r"""
import json, os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import jax
from repro.core.device_simulate import simulate_trace
from repro.distributed.mesh import make_shard_mesh
from repro.traces import zipf_trace

n = %(n)d
tr = zipf_trace(n, n_items=n - 5_000, alpha=0.9, seed=7)
kw = dict(assoc=8, shards=4)
mesh = make_shard_mesh(4)


def best_of(fn, reps=2):
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out

simulate_trace(tr, 8192, **kw)                                # compile
sh_wall, _ = best_of(lambda: simulate_trace(tr, 8192, **kw))
_, _, hs = simulate_trace(tr, 8192, return_state=True, **kw)
# exact chunked exchange (mesh_exchange="chunk", the default): the only
# collective is the entry/exit delta gather/split — must be bit-identical
simulate_trace(tr, 8192, mesh=mesh, **kw)                     # compile
m_wall, _ = best_of(lambda: simulate_trace(tr, 8192, mesh=mesh, **kw))
_, _, hm = simulate_trace(tr, 8192, mesh=mesh, return_state=True, **kw)
# speculative stale-global admission: one all-gather fold per merge epoch
simulate_trace(tr, 8192, mesh=mesh, mesh_exchange="stale", **kw)
s_wall, rs = best_of(lambda: simulate_trace(tr, 8192, mesh=mesh,
                                            mesh_exchange="stale", **kw))
print(json.dumps({
    "device": jax.devices()[0].platform,
    "mesh_devices": len(jax.devices()),
    "accesses": n,
    "sharded_1dev_acc_per_s": round(n / sh_wall),
    "mesh_acc_per_s": round(n / m_wall),
    "mesh_chunked_acc_per_s": round(n / m_wall),
    "mesh_stale_acc_per_s": round(n / s_wall),
    "mesh_overhead_vs_sharded": round(m_wall / sh_wall, 2),
    "mesh_stale_overhead_vs_sharded": round(s_wall / sh_wall, 2),
    "parity_ok": bool((np.asarray(hs) == np.asarray(hm)).all()),
}))
"""


def _mesh_subprocess_bench(quick: bool) -> dict:
    """Run the 2-forced-host-device mesh measurement in a CPU-only child;
    a failed or timed-out child fails the benchmark."""
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=os.path.join(_REPO_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # the script pins its own device count
    r = subprocess.run(
        [sys.executable, "-c",
         _MESH_BENCH_SCRIPT % {"n": 15_000 if quick else 30_000}],
        capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError("mesh bench child failed:\n" + r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def _best_of(fn, n=3):
    best, out = float("inf"), None
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def run(quick: bool = False):
    import jax
    from repro.core.device_simulate import simulate_trace, simulate_sweep
    from repro.kernels import ops, init_state, keys_to_lanes, make_config

    length = 60_000 if quick else 300_000
    C = 200 if quick else 1000
    tr = zipf_trace(length, n_items=length - 10_000, alpha=0.9, seed=7)
    warm = length // 5
    rows = []
    backend = jax.default_backend()

    # -- 1. trace engine: host loop vs device scan ---------------------------
    host_wall, host_res = _best_of(
        lambda: run_trace(WTinyLFU(C, sample_factor=8), tr, warmup=warm,
                          trace_name="golden-zipf"))
    simulate_trace(tr, C, warmup=warm)                    # compile once
    dev_wall, dev_res = _best_of(
        lambda: simulate_trace(tr, C, warmup=warm, trace_name="golden-zipf"))
    pal_len = min(length, 8192)                           # interpret is slow
    pal_wall, _ = _best_of(
        lambda: simulate_trace(tr[:pal_len], C, backend="pallas", chunk=1024),
        n=1)
    for name, wall, n, hr in [
        ("host run_trace", host_wall, length, host_res.hit_ratio),
        ("device jit scan", dev_wall, length, dev_res.hit_ratio),
        ("device pallas(interpret)", pal_wall, pal_len, None),
    ]:
        row = {"trace": "golden-zipf", "engine": name, "cache_size": C,
               "accesses": n, "wall_s": round(wall, 3),
               "acc_per_s": round(n / wall), "device": backend}
        if hr is not None:
            row["hit_ratio"] = hr
        rows.append(row)
        print(f"  {name:<26s} {n / wall:>12,.0f} acc/s"
              + (f"  hit={hr:.4f}" if hr is not None else ""), flush=True)
    print(f"  engine speedup (jit scan vs host): "
          f"{host_wall / dev_wall:.1f}x", flush=True)
    rows.append({"trace": "golden-zipf", "engine": "speedup:trace",
                 "speedup": round(host_wall / dev_wall, 2)})

    # -- 2. matrix throughput: Cartesian grid, one program vs python loop ----
    sizes = [C // 2, C] if quick else [250, 500, 1000]
    wfs = [0.01, 0.2]
    t0 = time.perf_counter()
    for sz in sizes:
        for wf in wfs:
            run_trace(WTinyLFU(sz, window_frac=wf, sample_factor=8), tr,
                      warmup=warm, trace_name="golden-zipf")
    host_mat = time.perf_counter() - t0
    simulate_sweep(tr, sizes, window_fracs=wfs, warmup=warm)   # compile once
    dev_mat, _ = _best_of(
        lambda: simulate_sweep(tr, sizes, window_fracs=wfs, warmup=warm,
                               trace_name="golden-zipf"), n=2)
    g = len(sizes) * len(wfs)
    print(f"  matrix({g} cfgs): host {g * length / host_mat:,.0f} "
          f"acc/s vs device {g * length / dev_mat:,.0f} acc/s "
          f"({host_mat / dev_mat:.1f}x)", flush=True)
    rows.append({"trace": "golden-zipf", "engine": "matrix", "grid": g,
                 "host_wall_s": round(host_mat, 2),
                 "device_wall_s": round(dev_mat, 2),
                 "speedup": round(host_mat / dev_mat, 2),
                 "device": backend})

    # -- 3. fused admission decisions: per-pair loop vs one batched launch ---
    # serving-tick shape: the sketch has seen the trace; a tick asks B
    # candidate-vs-victim verdicts.  The decision path is the one the old
    # kernels answered with three launches and the fused path answers in one.
    n_dec = min(length, 50_000)
    cands = tr[:n_dec].astype(np.uint64)
    victims = np.roll(cands, 1)
    # build the histograms (sequential by §3 semantics on both sides; timed
    # separately for the record)
    sk = default_sketch(C, sample_factor=8)
    adm = TinyLFUAdmission(sk)
    t0 = time.perf_counter()
    for k in cands.tolist():
        adm.record(k)
    host_rec = time.perf_counter() - t0
    cfg = make_config(C, sample_factor=8, counters_per_item=1.0)
    # the XLA path on every backend: off the TPU the Pallas kernels only
    # interpret, so this section times the same program everywhere (the
    # kernels' chip run is chip_smoke.py phase d)
    use_pallas = False
    clo, chi = keys_to_lanes(cands)
    vlo, vhi = keys_to_lanes(victims)
    state = ops.add(cfg, init_state(cfg), clo, chi, use_pallas)
    jax.block_until_ready(state["counters"])

    def host_decisions():
        return [adm.admit(c, v)
                for c, v in zip(cands.tolist(), victims.tolist())]

    host_dec, _ = _best_of(host_decisions)

    def dev_decisions():
        return ops.admit(cfg, state, clo, chi, vlo, vhi, use_pallas)

    np.asarray(dev_decisions())                           # compile once
    dev_dec, verdicts = _best_of(
        lambda: jax.block_until_ready(dev_decisions()))
    print(f"  admission: host {n_dec / host_dec:,.0f} dec/s vs device "
          f"{n_dec / dev_dec:,.0f} dec/s ({host_dec / dev_dec:.1f}x fused, "
          f"admit rate {float(np.asarray(verdicts).mean()):.2f}; "
          f"host record {n_dec / host_rec:,.0f} add/s)", flush=True)
    rows.append({"trace": "golden-zipf", "engine": "admission", "n": n_dec,
                 "host_wall_s": round(host_dec, 3),
                 "device_wall_s": round(dev_dec, 4),
                 "host_record_wall_s": round(host_rec, 3),
                 "speedup": round(host_dec / dev_dec, 1),
                 "device": backend})

    # -- 4. capacity scaling: flat O(C) argmin vs set-associative O(ways) ----
    # C=262144 pushes the UNSHARDED sketch width to 2^19 counters/row —
    # past the XLA-CPU gather-partitioning cliff at >= 2^18 that the
    # size-gated unrolled scalar-slice gathers fix (ISSUE 5; ROADMAP
    # "XLA-CPU cost-model cliffs"), so the 512 -> 262144 flatness ratio is
    # the regression tripwire for that fix (healthy ~0.75 — the unrolled
    # reads' constant cost — vs 0.28 measured with the cliff present)
    golden = (tr if length == 60_000
              else zipf_trace(60_000, n_items=50_000, alpha=0.9, seed=7))
    flat_caps = [512, 8192]
    assoc_caps = [512, 8192, 65536, 262144]
    acc = {}
    for label, caps, kw in [("scan(flat)", flat_caps, {}),
                            ("set-assoc(w=8)", assoc_caps, {"assoc": 8})]:
        for Cs in caps:
            simulate_trace(golden, Cs, **kw)             # compile once
            # best-of-4: the flatness ratio feeds the CI gate, and shared
            # dev boxes show LLC-contention dips of 30%+ on the large-C
            # point specifically (gate docstring has the noise model)
            wall, res = _best_of(
                lambda: simulate_trace(golden, Cs, trace_name="golden-zipf",
                                       **kw), n=4)
            acc[(label, Cs)] = len(golden) / wall
            rows.append({"trace": "golden-zipf", "engine": f"scaling:{label}",
                         "cache_size": Cs, "accesses": len(golden),
                         "wall_s": round(wall, 3),
                         "acc_per_s": round(len(golden) / wall),
                         "hit_ratio": res.hit_ratio, "device": backend})
            print(f"  {label:<16s} C={Cs:<6d} "
                  f"{len(golden) / wall:>12,.0f} acc/s", flush=True)
    speedup = acc[("set-assoc(w=8)", 8192)] / acc[("scan(flat)", 8192)]
    flatness = acc[("set-assoc(w=8)", 65536)] / acc[("set-assoc(w=8)", 512)]
    flatness_xl = (acc[("set-assoc(w=8)", 262144)]
                   / acc[("set-assoc(w=8)", 512)])
    print(f"  set-assoc vs flat at C=8192: {speedup:.1f}x; "
          f"flatness 512->65536 (1.0 = capacity-free): {flatness:.2f}; "
          f"512->262144 (width 2^19): {flatness_xl:.2f}", flush=True)
    rows.append({"trace": "golden-zipf", "engine": "speedup:set-assoc@8192",
                 "speedup": round(speedup, 2),
                 "flatness_512_to_65536": round(flatness, 2),
                 "flatness_512_to_262144": round(flatness_xl, 2)})

    # -- 5. adaptive window engine: per-access masks + epoch rebalance cost --
    from repro.core.device_simulate import ClimbSpec
    Ca = 8192
    kw_ad = {"assoc": 8, "adaptive": True, "climb": ClimbSpec()}
    simulate_trace(golden, Ca, **kw_ad)                  # compile once
    ad_wall, ad_res = _best_of(
        lambda: simulate_trace(golden, Ca, trace_name="golden-zipf", **kw_ad),
        n=2)
    ad_acc = len(golden) / ad_wall
    overhead = acc[("set-assoc(w=8)", Ca)] / ad_acc
    print(f"  adaptive(w=8)    C={Ca:<6d} {ad_acc:>12,.0f} acc/s "
          f"({overhead:.2f}x static cost, final quota "
          f"{ad_res.extra['final_quota']})", flush=True)
    rows.append({"trace": "golden-zipf", "engine": "adaptive(w=8)",
                 "cache_size": Ca, "accesses": len(golden),
                 "wall_s": round(ad_wall, 3), "acc_per_s": round(ad_acc),
                 "hit_ratio": ad_res.hit_ratio,
                 "static_over_adaptive": round(overhead, 2),
                 "device": backend})

    # -- 6. sharded sketch: delta-write path cost + flatness with shards on --
    sh_acc = {}
    for Cs in (512, 8192, 65536):
        kw_sh = {"assoc": 8, "shards": 4}
        simulate_trace(golden, Cs, **kw_sh)              # compile once
        wall, sh_res = _best_of(
            lambda: simulate_trace(golden, Cs, trace_name="golden-zipf",
                                   **kw_sh), n=4 if Cs != 8192 else 2)
        sh_acc[Cs] = len(golden) / wall
        rows.append({"trace": "golden-zipf", "engine": "scaling:sharded(s=4)",
                     "cache_size": Cs, "accesses": len(golden),
                     "wall_s": round(wall, 3),
                     "acc_per_s": round(len(golden) / wall),
                     "hit_ratio": sh_res.hit_ratio, "device": backend})
        print(f"  sharded(s=4,w=8) C={Cs:<6d} "
              f"{len(golden) / wall:>12,.0f} acc/s", flush=True)
    sh_overhead = acc[("set-assoc(w=8)", 8192)] / sh_acc[8192]
    sh_flatness = sh_acc[65536] / sh_acc[512]
    print(f"  sharded vs unsharded at C=8192: {sh_overhead:.2f}x cost; "
          f"sharded flatness 512->65536: {sh_flatness:.2f}", flush=True)
    rows.append({"trace": "golden-zipf", "engine": "speedup:sharded@8192",
                 "unsharded_over_sharded": round(sh_overhead, 2),
                 "flatness_512_to_65536": round(sh_flatness, 2)})

    # -- 7. multi-device mesh run (ISSUE 5/6): 2 forced host devices ---------
    # forcing the host device count only works before jax initializes, so
    # the mesh measurement runs in a CPU-only subprocess: single-device
    # sharded, exact chunked-exchange mesh, and speculative stale-global mesh
    # on the same trace in the same environment, reporting throughput +
    # bitwise parity of the chunked hit sequence.  Its "device" is the
    # child's platform (cpu), whatever the parent runs on.
    mesh = _mesh_subprocess_bench(quick)
    rows.append({"trace": "golden-zipf", "engine": "mesh(s=4,d=2)", **mesh})
    print(f"  mesh(s=4,d=2,{mesh['device']}) C=8192 "
          f"{mesh['mesh_acc_per_s']:>12,.0f} "
          f"acc/s ({mesh['mesh_overhead_vs_sharded']:.1f}x sharded cost, "
          f"parity {'OK' if mesh['parity_ok'] else 'BROKEN'}; stale "
          f"{mesh['mesh_stale_acc_per_s']:,.0f} acc/s, "
          f"{mesh['mesh_stale_overhead_vs_sharded']:.1f}x)",
          flush=True)

    # -- 8. checkpoint overhead (ISSUE 7): epoch-boundary snapshot cost ------
    # same config as the section-6 sharded baseline (assoc=8, shards=4,
    # C=8192) so sh_acc[8192] is the plain-run denominator; the auto
    # cadence (one snapshot per ~32k accesses) segments the scan and writes
    # async checkpoints — the acceptance bar is <= 10% over plain, and
    # check_bench RECORDS the ratio without gating it (disk speed on CI
    # runners is not a property of this code)
    import shutil
    import tempfile
    from repro.core.device_simulate import DeviceWTinyLFU
    cfg_ck = DeviceWTinyLFU(8192, assoc=8, shards=4)
    ckd = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        cfg_ck.run(golden, checkpoint_dir=ckd)           # compile segments
        ck_wall, ck_res = _best_of(
            lambda: cfg_ck.run(golden, checkpoint_dir=ckd), n=2)
    finally:
        shutil.rmtree(ckd, ignore_errors=True)
    ck_acc = len(golden) / ck_wall
    ck_overhead = sh_acc[8192] / ck_acc
    print(f"  checkpointed(s=4,w=8) C=8192 {ck_acc:>9,.0f} acc/s "
          f"({ck_overhead:.2f}x plain sharded run, auto cadence "
          f"{ck_res.extra['checkpoint_every']})", flush=True)
    rows.append({"trace": "golden-zipf", "engine": "checkpointed(s=4,w=8)",
                 "cache_size": 8192, "accesses": len(golden),
                 "wall_s": round(ck_wall, 3), "acc_per_s": round(ck_acc),
                 "checkpoint_every": ck_res.extra["checkpoint_every"],
                 "checkpoint_overhead_vs_plain": round(ck_overhead, 2),
                 "device": backend})

    # -- 9. multi-stream batched engine (ISSUE 8): lane dispatch amortization
    # Frozen small-tenant geometry (the regime the lane axis exists for —
    # thousands of tiny per-tenant caches, where the unbatched step is
    # bound by per-op dispatch cost, ~0.7us/op on 1-core CI CPUs, not by
    # bandwidth): C=16 per tenant (window 1 + main 15, protected 12),
    # W=128, cap=15, 16x4 sketch, 64-bit doorkeeper.  Kernel-level
    # step_ref with unroll=2 (best measured; 4+ bloats the while body).
    # Aggregate acc/s at B=64 vs B=1 is the scaling the CI gate tracks.
    from dataclasses import replace as _sreplace
    from repro.kernels.sketch_step import (StepSpec, init_step_state,
                                           make_step_params, step_ref)
    Ts = 8_000 if quick else 20_000
    tspec = StepSpec(width=16, rows=4, dk_bits=64, window_slots=1,
                     main_slots=16)
    tparams = make_step_params(1, 15, 12, 128, 15)
    ttr = tenant_lanes_trace(64, Ts, n_items=2000, alpha=1.1, seed=7)
    tlo64, thi64 = keys_to_lanes(ttr.astype(np.uint64))
    st_acc = {}
    for Bn in (1, 16, 64):
        bspec = _sreplace(tspec, streams=Bn)
        bstate = init_step_state(bspec, 1, 15)
        sl = np.s_[0] if Bn == 1 else np.s_[:Bn]
        blo = np.asarray(tlo64)[sl].astype(np.int32)
        bhi = np.asarray(thi64)[sl].astype(np.int32)

        def lane_step(p, s, l, h, _sp=bspec):
            return step_ref(_sp, p, s, l, h, unroll=2)

        fn = jax.jit(lane_step)
        jax.block_until_ready(fn(tparams, bstate, blo, bhi)[1])  # compile
        wall, _ = _best_of(lambda: jax.block_until_ready(
            fn(tparams, bstate, blo, bhi)[1]), n=3)
        st_acc[Bn] = Bn * Ts / wall
        rows.append({"trace": "tenant-lanes", "engine": f"streams(B={Bn})",
                     "cache_size": 16, "accesses": Bn * Ts,
                     "wall_s": round(wall, 3),
                     "acc_per_s": round(st_acc[Bn]), "device": backend})
        print(f"  streams(B={Bn:<3d}) C=16   {st_acc[Bn]:>12,.0f} acc/s "
              f"aggregate", flush=True)
    st_scaling = st_acc[64] / st_acc[1]
    print(f"  streams scaling B=1 -> B=64 (aggregate): {st_scaling:.2f}x",
          flush=True)
    rows.append({"trace": "tenant-lanes", "engine": "speedup:streams@64",
                 "scaling_1_to_64": round(st_scaling, 2)})

    # -- 10. policy panel (ISSUE 9): competitors in the same fused scan ------
    # S3-FIFO / ARC / heap-free-LFU share the set-associative machinery with
    # W-TinyLFU (identical geometry: C=8192, assoc=8), so their acc/s should
    # land within ~2x of the default policy — a bigger gap means one of the
    # policy branches broke out of the fused per-access shape (check_bench
    # arm 8 warns on it; ARC's ~4.5x is a KNOWN cost, not a break — see
    # docs/BENCHMARKS.md arm 8).  ARC needs the doorkeeper (ghost lists
    # live in the Bloom slices); s3fifo gets window_frac=0.1 (small-queue
    # share, the documented operating point).
    pol_acc = {}
    Cp = 8192
    for pol in ("wtinylfu", "s3fifo", "arc", "lfu"):
        kw_p = {"assoc": 8}
        if pol != "wtinylfu":
            kw_p["policy"] = pol
        if pol == "s3fifo":
            kw_p["window_frac"] = 0.1
        simulate_trace(golden, Cp, **kw_p)               # compile once
        wall, p_res = _best_of(
            lambda: simulate_trace(golden, Cp, trace_name="golden-zipf",
                                   **kw_p), n=2)
        pol_acc[pol] = len(golden) / wall
        rows.append({"trace": "golden-zipf", "engine": f"policy:{pol}",
                     "cache_size": Cp, "accesses": len(golden),
                     "wall_s": round(wall, 3),
                     "acc_per_s": round(pol_acc[pol]),
                     "hit_ratio": p_res.hit_ratio, "device": backend})
        print(f"  policy:{pol:<9s} C={Cp:<6d} {pol_acc[pol]:>12,.0f} acc/s "
              f"hit={p_res.hit_ratio:.4f}", flush=True)
    pol_worst = min(pol_acc[p] / pol_acc["wtinylfu"]
                    for p in ("s3fifo", "arc", "lfu"))
    print(f"  slowest competitor vs w-tinylfu: {pol_worst:.2f}x", flush=True)

    # -- perf snapshot at the repo root: the numbers CI tracks across PRs ----
    snapshot = {
        "device": backend,
        "machine": _machine_fingerprint(),
        "trace_engine_acc_per_s": round(length / dev_wall),
        "assoc_acc_per_s_small_C": round(acc[("set-assoc(w=8)", 512)]),
        "assoc_acc_per_s_large_C": round(acc[("set-assoc(w=8)", 65536)]),
        "flat_acc_per_s_8192": round(acc[("scan(flat)", 8192)]),
        "assoc_speedup_vs_flat_8192": round(speedup, 2),
        "assoc_flatness_512_to_65536": round(flatness, 2),
        "adaptive_acc_per_s_8192": round(ad_acc),
        "adaptive_overhead_vs_static": round(overhead, 2),
        "assoc_acc_per_s_xl_C": round(acc[("set-assoc(w=8)", 262144)]),
        "assoc_flatness_512_to_262144": round(flatness_xl, 2),
        "sharded_acc_per_s_8192": round(sh_acc[8192]),
        "sharded_overhead_vs_unsharded": round(sh_overhead, 2),
        "sharded_flatness_512_to_65536": round(sh_flatness, 2),
        "batched_dec_per_s": round(n_dec / dev_dec),
        "checkpoint_acc_per_s_8192": round(ck_acc),
        "checkpoint_overhead_vs_plain": round(ck_overhead, 2),
        "streams_acc_per_s_single": round(st_acc[1]),
        "streams_acc_per_s_total": round(st_acc[64]),
        "streams_scaling_1_to_64": round(st_scaling, 2),
        "policy_acc_per_s_wtinylfu": round(pol_acc["wtinylfu"]),
        "policy_acc_per_s_s3fifo": round(pol_acc["s3fifo"]),
        "policy_acc_per_s_arc": round(pol_acc["arc"]),
        "policy_acc_per_s_lfu": round(pol_acc["lfu"]),
    }
    snapshot.update({
        "mesh_device": mesh["device"],
        "mesh_devices": mesh["mesh_devices"],
        "mesh_acc_per_s_8192": round(mesh["mesh_acc_per_s"]),
        "mesh_chunked_acc_per_s_8192": round(mesh["mesh_chunked_acc_per_s"]),
        "mesh_stale_acc_per_s_8192": round(mesh["mesh_stale_acc_per_s"]),
        "mesh_overhead_vs_sharded": round(mesh["mesh_overhead_vs_sharded"],
                                          2),
        "mesh_stale_overhead_vs_sharded": round(
            mesh["mesh_stale_overhead_vs_sharded"], 2),
        "mesh_parity_ok": mesh["parity_ok"]})
    with open(os.path.join(_REPO_ROOT, "BENCH_device.json"), "w") as f:
        json.dump(snapshot, f, indent=1)

    save(rows, "device_throughput")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    run()
