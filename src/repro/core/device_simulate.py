"""Device-resident W-TinyLFU trace simulation engine.

The host engine (`simulate.run_trace`) walks a Python per-access loop at
~µs/access; the paper's hit-ratio curves (§5, Figs 6-22) need millions of
accesses × dozens of (policy, size, window) configurations, which makes the
host loop wall-clock prohibitive at production scale.  This module runs the
*entire* trace on the accelerator instead:

* the fused step (kernels/sketch_step.py) advances sketch + window-LRU +
  SLRU-main through a chunk of accesses in one VMEM-resident launch;
* `jax.lax.scan` chains chunks so a whole trace is one compiled program —
  hit counts come back as a single scalar, keys stream device-side;
* `simulate_sweep` vmaps the scan over a *grid* of configurations
  (cache sizes × window fractions × seed traces), turning a `run_matrix`
  Cartesian experiment into one compiled program.

Backends (`backend=` argument):

* ``"jit"``     — the pure-jnp twin (`step_ref`) under `jax.jit`.  This is the
                  fast path on CPU and the only path `vmap` currently takes.
* ``"pallas"``  — the fused Pallas kernel, `interpret=True` off-TPU.  Same
                  bits, real VMEM residency + buffer donation on TPU.

Sizing mirrors the host `WTinyLFU` defaults exactly (window 1%, SLRU 80/20,
W = sample_factor·C, cap = W/C with the doorkeeper absorbing one count), so
host and device hit ratios are directly comparable: the only difference is
the hash family (64-bit splitmix on host vs 32-bit-lane mixers on device),
which perturbs hit ratios by well under ±0.005 on the golden traces
(tests/test_device_simulate.py pins this).

Keys are int64/uint64 host arrays; they are split once into (lo, hi) 32-bit
lanes on the way in (TPU has no 64-bit integer multiply — DESIGN.md §2).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.sketch_step import (StepSpec, MESH_AXIS, make_step_params,
                                       init_step_state, step_ref, step_pallas,
                                       rebalance, _state_keys,
                                       R_HITS, R_WQUOTA, R_EHITS, R_CANDS,
                                       R_ADMIT, R_REJECT, R_RESETS)
from repro.kernels.sketch_common import keys_to_lanes, POLICIES
from repro.kernels.sketch_merge import merge_halve, merge_halve_mesh
from . import adaptive
from .hashing import assoc_geometry, slots_for
from .sketch import _pow2ceil
from .simulate import SimResult


@dataclass(frozen=True)
class DeviceWTinyLFU:
    """One simulated W-TinyLFU configuration (host-side description).

    ``assoc=None`` uses the exact flat tables (global LRU/SLRU, O(capacity)
    per access); ``assoc=W`` uses W-way set-associative tables (per-set
    LRU/SLRU, O(W) per access — the production-scale path).
    ``counter_bits=8`` doubles the sketch footprint but lifts the counter cap
    from 15 to 255, so ``sample_factor`` above 16 no longer needs the host
    engine.

    ``shards=S`` (pow2 > 1) partitions the frequency sketch into S
    device-resident shards: per-access writes touch only the owning shard's
    delta slice and a fused ``merge_halve`` folds the deltas into the global
    estimate every ``merge_every`` accesses — inside the compiled program,
    no host sync (kernels/sketch_merge.py).  ``merge_every=0`` auto-sizes to
    ``min(4096, sample_size)`` so the deferred §3.3 aging stays within one
    reset period of the per-access schedule.

    ``mesh=`` (a 1-D ``("shard",)`` mesh from
    ``distributed.mesh.make_shard_mesh``) executes the sharded run over
    MULTIPLE devices: the delta halves become shard-major arrays
    partitioned along the mesh axis (block placement — device ``d`` owns
    shards ``[d*S/D, (d+1)*S/D)``, matching
    ``distributed.mesh.shard_placement``), the global halves and cache
    tables are replicated, and the per-access path exchanges NOTHING —
    all cross-device traffic is per-epoch-chunk or rarer, selected by
    ``mesh_exchange`` (it used to be one 2-int ``psum`` per access, a 62x
    overhead on the forced-2-device bench):

    * ``"chunk"`` (default, exact): one all-gather of the delta blocks on
      entering the compiled program composes the single-device
      [global || delta] layout on every device, each device then replays
      the identical epoch-chunked single-device program (step scan +
      ``merge_halve`` fold, which keeps the deltas self-contained), and
      the local delta blocks are sliced back out at exit.  Bit-identical
      to the single-device sharded run — same hit sequence, same final
      sketch state (tests/test_distributed.py pins this over forced host
      devices).
    * ``"stale"`` (speculative): per-access delta writes stay
      device-local and admission estimates read only the replicated
      global halves — stale by at most one merge epoch — so the one
      collective is the per-epoch ``merge_halve_mesh`` all-gather fold
      that reconciles the deltas.  Lands in the goldens-±0.01 tier of the
      exactness ladder, with the host twin
      ``WTinyLFU(stale_admission=True)``.

    Requires ``shards % n_devices == 0`` and ``backend="jit"``.

    ``integrity=True`` (requires ``shards > 1``) arms the self-healing
    integrity fold: per-shard checksums over the global sketch halves are
    verified and refreshed at every merge boundary, and a mismatched
    (corrupted) shard is quarantined — its slices zeroed, its counts
    re-learned by the §3.3 aging within a few sample periods
    (kernels/sketch_merge.py).

    ``events=True`` (set-associative W-TinyLFU only: ``assoc=W``,
    ``policy="wtinylfu"``, ``shards=1``, ``adaptive=False``) counts the
    admission events of a run in the step's registers, at no extra
    transfer: window overflows that pushed a candidate, candidates
    admitted over a resident victim, candidates rejected by it, and §3.3
    resets.  Runs return them as ``SimResult.extra["events"]``.  False
    compiles the identical program.

    ``run()`` is the general entry point — it adds epoch-boundary
    checkpointing (``checkpoint_dir=``/``checkpoint_every=``) on top of
    what ``simulate_trace`` does; :func:`resume_trace` restores the latest
    checkpoint and continues bit-identically.
    """
    capacity: int
    window_frac: float = 0.01
    sample_factor: int = 8
    protected_frac: float = 0.8
    counters_per_item: float = 1.0
    rows: int = 4
    doorkeeper: bool = True
    dk_bits_per_item: float = 4.0
    assoc: int | None = None
    counter_bits: int = 4
    adaptive: bool = False        # runtime hill-climbed window quota
    window_max_frac: float = 0.5  # adaptive: table headroom for the climb
    shards: int = 1               # sketch shards; >1 = delta/global split
    merge_every: int = 0          # sharded merge cadence; 0 = auto
    mesh: object = None           # ("shard",) mesh; None = single device
    mesh_exchange: str = "chunk"  # mesh cadence: "chunk" exact | "stale"
    integrity: bool = False       # checksum + shard-quarantine merge fold
    streams: int = 1              # lane-batched tenant caches per program
    policy: str = "wtinylfu"      # device policy panel: s3fifo | arc | lfu
    events: bool = False          # admission event counters in the regs

    def __post_init__(self):
        # eager validation (ISSUE 7): bad values used to surface as XLA
        # shape errors (or assertion tracebacks) from deep inside the
        # compile path — fail at construction with actionable messages
        # instead.  simulate_sweep builds one DeviceWTinyLFU per grid
        # point, so sweeps inherit every check.
        if self.capacity < 1:
            raise ValueError(f"capacity {self.capacity} must be >= 1")
        if not 0.0 < self.window_frac < 1.0:
            raise ValueError(f"window_frac {self.window_frac} must be in "
                             "(0, 1) — it is the window's share of capacity")
        if not 0.0 < self.protected_frac < 1.0:
            raise ValueError(f"protected_frac {self.protected_frac} must be "
                             "in (0, 1)")
        if self.sample_factor < 1:
            raise ValueError(f"sample_factor {self.sample_factor} must be "
                             ">= 1 (W = sample_factor * capacity)")
        if self.counter_bits not in (4, 8):
            raise ValueError(f"counter_bits {self.counter_bits} must be 4 "
                             "(paper §3.4.1 nibbles) or 8 (byte counters)")
        if self.rows < 1:
            raise ValueError(f"rows {self.rows} must be >= 1")
        if self.assoc is not None and self.assoc < 1:
            raise ValueError(f"assoc {self.assoc} must be >= 1 ways (or "
                             "None for the flat exact tables)")
        if self.shards < 1 or (self.shards & (self.shards - 1)):
            raise ValueError(f"shards {self.shards} must be a power of two "
                             "(shard membership is a masked hash)")
        if self.merge_every < 0:
            raise ValueError(f"merge_every {self.merge_every} must be >= 0 "
                             "(0 = auto min(4096, sample_size))")
        if self.mesh_exchange not in ("chunk", "stale"):
            raise ValueError(f"mesh_exchange {self.mesh_exchange!r} must be "
                             "'chunk' or 'stale'")
        if self.integrity and self.shards <= 1:
            raise ValueError("integrity=True requires shards > 1: the "
                             "checksums cover the per-shard global sketch "
                             "halves, which only exist in sharded mode")
        if self.streams < 1:
            raise ValueError(f"streams {self.streams} must be >= 1 (the "
                             "number of lane-batched tenant caches; 1 = "
                             "the unbatched single-stream engine)")
        if self.streams > 1 and self.mesh is not None:
            raise ValueError(
                f"streams {self.streams} cannot combine with mesh=: lanes "
                "batch WHOLE per-tenant engines while the mesh partitions "
                "ONE engine's sketch across devices — shard tenants over "
                "meshes at the process level instead")
        if self.policy not in POLICIES:
            raise ValueError(f"policy {self.policy!r} must be one of "
                             f"{POLICIES}")
        if self.policy != "wtinylfu":
            if self.assoc is None:
                raise ValueError(
                    f"policy {self.policy!r} requires assoc= (the "
                    "competitor panel reuses the set-associative table "
                    "machinery; the flat exact tables are W-TinyLFU-only)")
            if self.shards > 1 or self.mesh is not None:
                raise ValueError(
                    f"policy {self.policy!r} cannot combine with shards/"
                    "mesh: the sharded sketch split serves the TinyLFU "
                    "admission filter — competitors run single-sketch")
            if self.adaptive:
                raise ValueError(
                    f"policy {self.policy!r} cannot combine with "
                    "adaptive=True: the hill-climbed quota rebalances the "
                    "W-TinyLFU window/main split (arc adapts its own "
                    "target p as runtime state instead)")
            if self.integrity:
                raise ValueError(
                    f"policy {self.policy!r} cannot combine with "
                    "integrity=True (it requires shards > 1)")
        if self.events and (self.assoc is None or self.policy != "wtinylfu"
                            or self.shards > 1 or self.adaptive):
            raise ValueError(
                "events=True counts the set-associative W-TinyLFU step's "
                "admissions: it requires assoc=W, policy 'wtinylfu', "
                "shards=1 and adaptive=False")
        if self.policy == "arc" and not self.doorkeeper:
            raise ValueError(
                "policy 'arc' requires doorkeeper=True: the B1/B2 ghost "
                "lists are Bloom halves addressed by the doorkeeper probe "
                "schedule, so dk_bits must be sized (> 0)")

    @property
    def window_cap(self) -> int:
        # arc/lfu run main-table-only: the window table stays allocated at
        # its 1-entry minimum and the kernels never touch it
        if self.policy in ("arc", "lfu"):
            return 1
        return max(1, int(round(self.capacity * self.window_frac)))

    @property
    def main_cap(self) -> int:
        # arc/lfu: the main table IS the cache (no window share)
        if self.policy in ("arc", "lfu"):
            return max(1, self.capacity)
        return max(1, self.capacity - self.window_cap)

    @property
    def window_cap_max(self) -> int:
        """Largest quota the adaptive tables can host (static headroom)."""
        if not self.adaptive:
            return self.window_cap
        return adaptive.window_cap_max(self.capacity, self.window_cap,
                                       self.window_max_frac)

    @property
    def main_cap_max(self) -> int:
        """Largest main capacity (window quota at its minimum of 1)."""
        return max(1, self.capacity - 1)

    @property
    def prot_cap(self) -> int:
        return max(1, int(self.main_cap * self.protected_frac))

    @property
    def sample_size(self) -> int:
        return self.sample_factor * self.capacity

    @property
    def cap(self) -> int:
        cmax = (1 << self.counter_bits) - 1
        return min(cmax, max(1, self.sample_factor
                             - (1 if self.doorkeeper else 0)))

    @property
    def width(self) -> int:
        w = _pow2ceil(int(max(1.0, self.counters_per_item * self.sample_size
                              / self.rows)))
        # sharded: each shard needs at least one packed word per row
        return max(8 * self.shards, w)

    @property
    def dk_bits(self) -> int:
        if not self.doorkeeper:
            return 0
        # sharded: each shard needs at least one 32-bit doorkeeper word
        return max(32 * self.shards, _pow2ceil(int(self.sample_size
                                                   * self.dk_bits_per_item)))

    @property
    def merge_epoch(self) -> int:
        """Resolved sharded merge cadence (accesses between merge_halve
        folds).  ``merge_every=0`` auto-sizes to ``min(4096, sample_size)``:
        never defer the §3.3 aging past one reset period, and never merge
        less often than the adaptive default epoch."""
        return self.merge_every or max(1, min(4096, self.sample_size))

    @property
    def ways(self) -> int | None:
        """Static gather width in set mode: >= assoc, from the main table's
        geometry (the window shares it so both tables use one block shape).
        Adaptive sizing uses the LARGEST main capacity the climb can reach."""
        if self.assoc is None:
            return None
        return assoc_geometry(self.main_cap_max if self.adaptive
                              else self.main_cap, self.assoc)[1]

    def _table_slots(self, cap: int, ways: int | None = None) -> int:
        """Static slots to host ``cap`` entries: the capacity itself (flat),
        or pow2 sets × ways (set-associative) with the excess marked padding
        at init.  ``ways`` overrides for vmapped sweeps sharing the largest
        configuration's block shape."""
        if self.assoc is None:
            return cap
        return slots_for(cap, ways or self.ways)

    def spec(self, window_slots: int | None = None,
             main_slots: int | None = None,
             ways: int | None = None) -> StepSpec:
        """Static geometry; slots may be padded up for vmapped sweeps.
        Adaptive mode sizes both tables for the climb's full quota range
        (window up to ``window_max_frac``, main up to capacity - 1)."""
        wsize = self.window_cap_max if self.adaptive else self.window_cap
        msize = self.main_cap_max if self.adaptive else self.main_cap
        return StepSpec(
            width=self.width, rows=self.rows, dk_bits=self.dk_bits,
            window_slots=window_slots or self._table_slots(wsize),
            main_slots=main_slots or self._table_slots(msize),
            assoc=(ways or self.ways) if self.assoc is not None else None,
            counter_bits=self.counter_bits, adaptive=self.adaptive,
            shards=self.shards, mesh_devices=self.mesh_devices,
            # normalized so single-device specs share one compile cache key
            mesh_exchange=self.mesh_exchange if self.mesh is not None
            else "chunk", integrity=self.integrity, streams=self.streams,
            policy=self.policy, events=self.events)

    @property
    def mesh_devices(self) -> int:
        """Devices of the ``("shard",)`` mesh (0 = single-device layout)."""
        if self.mesh_exchange not in ("chunk", "stale"):
            raise ValueError(f"mesh_exchange {self.mesh_exchange!r} must be "
                             "'chunk' or 'stale'")
        if self.mesh is None:
            if self.mesh_exchange != "chunk":
                raise ValueError("mesh_exchange='stale' requires mesh= (a "
                                 "('shard',) mesh from "
                                 "distributed.mesh.make_shard_mesh)")
            return 0
        if tuple(self.mesh.axis_names) != ("shard",):
            raise ValueError(f"mesh axes {self.mesh.axis_names} != "
                             "('shard',) — build it with "
                             "distributed.mesh.make_shard_mesh")
        n = int(self.mesh.devices.size)
        if self.shards <= 1:
            raise ValueError("mesh execution requires shards > 1")
        if self.shards % n:
            raise ValueError(f"shards {self.shards} must be a multiple of "
                             f"the mesh size {n} (block placement)")
        return n

    def params(self, warmup: int = 0) -> jnp.ndarray:
        return make_step_params(self.window_cap, self.main_cap, self.prot_cap,
                                self.sample_size, self.cap, warmup,
                                counter_bits=self.counter_bits)

    def run(self, trace, *, warmup: int = 0, backend: str = "jit",
            chunk: int = 512, interpret: bool | None = None,
            trace_name: str = "?", climb: "ClimbSpec | None" = None,
            checkpoint_dir: str | None = None, checkpoint_every: int = 0,
            return_state: bool = False, on_checkpoint=None,
            fault_hook=None):
        """Simulate ``trace`` with optional epoch-boundary checkpointing.

        Without ``checkpoint_dir`` this is ``simulate_trace`` for this
        configuration (one compiled program over the whole trace).  With
        it, the trace is segmented at merge-epoch boundaries — every chunk
        boundary is already a clean state handoff, so segmented execution
        is bit-identical to the single-program run — and the full engine
        state tree (sketch halves, cache tables, climb registers, hit
        prefix, trace cursor) is snapshotted via
        ``checkpoint.store.AsyncCheckpointer`` after each segment.
        :func:`resume_trace` restores the latest complete checkpoint and
        continues the run, reproducing the uninterrupted hit sequence and
        final sketch words exactly.

        ``checkpoint_every`` (accesses) must be a positive multiple of the
        run's epoch — ``climb.epoch_len`` (adaptive), ``merge_epoch``
        (sharded), anything (unsharded static) — 0 auto-sizes to roughly
        32k accesses rounded to whole epochs.  Checkpointing requires
        ``backend="jit"`` (the segmented scan is the jit scan).

        ``on_checkpoint(cursor)`` fires after each snapshot is queued (the
        fault-injection harness prints its kill markers from it);
        ``fault_hook(cursor, state) -> state | None`` runs between
        segments on the canonical single-device state layout and may
        return a mutated state — the injection point for corruption
        experiments (``core.faults``).
        """
        return _run_checkpointed(
            self, trace, warmup=warmup, backend=backend, chunk=chunk,
            interpret=interpret, trace_name=trace_name, climb=climb,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            return_state=return_state, on_checkpoint=on_checkpoint,
            fault_hook=fault_hook)


def _trace_lanes(trace: np.ndarray):
    lo, hi = keys_to_lanes(np.asarray(trace).astype(np.uint64))
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _check_trace_streams(cfg: "DeviceWTinyLFU", trace: np.ndarray):
    """Eager trace-shape vs ``streams`` validation (PR 7 style): a mismatch
    must raise a ValueError naming the field, not a compiled-shape error
    from deep inside the vmapped scan."""
    trace = np.asarray(trace)
    if cfg.streams > 1:
        if trace.ndim != 2 or trace.shape[0] != cfg.streams:
            raise ValueError(
                f"streams {cfg.streams} expects a (B, T) = ({cfg.streams}, "
                f"T) trace — one key row per tenant lane; got trace shape "
                f"{tuple(trace.shape)}")
    elif trace.ndim != 1:
        raise ValueError(
            f"trace shape {tuple(trace.shape)} carries a lane axis but "
            "streams is 1 (the unbatched engine, bit-identical to a 1-D "
            f"run) — construct DeviceWTinyLFU(streams={trace.shape[0]}) "
            "to batch tenant lanes, or pass a 1-D trace")


# ---------------------------------------------------------------------------
# single-trace simulation
# ---------------------------------------------------------------------------

# module-level jit wrappers/caches: jax's trace cache is keyed on the
# wrapper object, so per-call jax.jit(...) would retrace and recompile the
# whole scan every invocation.  The dict memos are bounded like _mesh_cache
# (PR 6): a geometry sweep mints a fresh spec per grid point and every
# entry pins a compiled executable, so unbounded memos grow without limit
_jit_step = jax.jit(step_ref, static_argnums=(0,))
_pallas_cache: dict = {}
_vmap_cache: dict = {}
_STEP_CACHE_LIMIT = 32


def _run_jit(spec: StepSpec, params, state, lo, hi):
    return _jit_step(spec, params, state, lo, hi)


def _chunk_lanes(x, nc: int, L: int):
    """(..., nc*L) -> scan-major (nc, ..., L): the chunk axis leads (scan
    iterates over it) and the lane axis, if any, rides along so each scan
    step sees per-lane (B, L) key rows."""
    if x.ndim == 1:
        return x.reshape(nc, L)
    return x.reshape(x.shape[0], nc, L).swapaxes(0, 1)


def _pallas_runner(spec: StepSpec, interpret: bool):
    key = (spec, interpret)
    if key not in _pallas_cache:
        if len(_pallas_cache) >= _STEP_CACHE_LIMIT:
            _pallas_cache.clear()
        @jax.jit
        def run(params, state, los, his, nvalid):
            def body(st, x):
                clo, chi, nv = x
                st, hits = step_pallas(spec, params, st, clo, chi, nv,
                                       interpret=interpret)
                return st, hits
            return jax.lax.scan(body, state, (los, his, nvalid))
        _pallas_cache[key] = run
    return _pallas_cache[key]


def _run_pallas(spec: StepSpec, params, state, lo, hi, chunk: int,
                interpret: bool):
    n = lo.shape[-1]
    pad = (-n) % chunk
    if pad:
        z = jnp.zeros(lo.shape[:-1] + (pad,), lo.dtype)
        lo = jnp.concatenate([lo, z], axis=-1)
        hi = jnp.concatenate([hi, z], axis=-1)
    nchunks = lo.shape[-1] // chunk
    los = _chunk_lanes(lo, nchunks, chunk)
    his = _chunk_lanes(hi, nchunks, chunk)
    # lanes share the chunking (one (B, T) trace, one T), so nvalid stays a
    # per-chunk scalar that every lane's masked tail consumes identically
    nvalid = jnp.minimum(
        jnp.maximum(n - jnp.arange(nchunks, dtype=jnp.int32) * chunk, 0),
        chunk)
    state, hits = _pallas_runner(spec, interpret)(params, state, los, his,
                                                  nvalid)
    if spec.streams > 1:                     # (nc, B, chunk) -> (B, T)
        return state, hits.swapaxes(0, 1).reshape(spec.streams, -1)[:, :n]
    return state, hits.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# sharded sketches: epoch-chunked scan + in-program merge_halve
# ---------------------------------------------------------------------------

_sharded_cache: dict = {}
_mesh_cache: dict = {}
# compiled mesh runners are keyed on (spec, mesh, adaptive); a geometry sweep
# mints a fresh spec per grid point, and each entry pins a compiled
# multi-device executable — bound the memo like the host set-index memos
_MESH_CACHE_LIMIT = 32


def _mesh_state_specs(spec: StepSpec):
    """shard_map in/out partition specs for the mesh-layout state pytree:
    the shard-major delta arrays ride the ("shard",) axis, everything else
    (global sketch halves, cache tables, registers) is replicated."""
    from jax.sharding import PartitionSpec as P
    return {k: (P("shard") if k in ("dcounters", "ddoorkeeper") else P())
            for k in _state_keys(spec)}


def _from_mesh_state(spec: StepSpec, state: dict) -> dict:
    """Mesh-layout state -> the single-device [global || delta] layout, so
    callers (and the parity tests) compare final sketch words directly."""
    out = {k: v for k, v in state.items()
           if k not in ("dcounters", "ddoorkeeper")}
    delta = state["dcounters"].transpose(1, 0, 2).reshape(spec.counter_words)
    out["counters"] = jnp.concatenate([state["counters"], delta])
    ddk = (state["ddoorkeeper"].reshape(spec.dk_words) if spec.dk_bits
           else jnp.zeros_like(state["doorkeeper"]))
    out["doorkeeper"] = jnp.concatenate([state["doorkeeper"], ddk])
    return out


def _to_mesh_state(spec: StepSpec, state: dict) -> dict:
    """Inverse of :func:`_from_mesh_state`: the canonical single-device
    [global || delta] layout -> the mesh (shard-major delta) layout.  This
    is the elastic-restore path — checkpoints always store the canonical
    layout, so a snapshot taken on ANY mesh size (including a plain
    single-device run) re-shards onto any other mesh whose size divides
    ``spec.shards``."""
    H, HD = spec.counter_words, spec.dk_words
    out = {k: v for k, v in state.items()
           if k not in ("counters", "doorkeeper")}
    out["counters"] = state["counters"][:H]
    out["doorkeeper"] = state["doorkeeper"][:HD]
    out["dcounters"] = state["counters"][H:].reshape(
        spec.rows, spec.shards, spec.wps_shard).transpose(1, 0, 2)
    out["ddoorkeeper"] = (
        state["doorkeeper"][HD:].reshape(spec.shards, spec.dkw_shard)
        if spec.dk_bits
        else jnp.zeros((spec.shards, spec.dkw_shard), jnp.int32))
    return out


def _gather_delta_state(spec: StepSpec, state: dict) -> dict:
    """Inside the shard_map body: all-gather the device-local delta blocks
    and compose the single-device [global || delta] layout on EVERY device
    — the one collective of the exact ``mesh_exchange="chunk"`` mode, paid
    once on entering the compiled program (the epoch fold keeps the
    replicated replica self-contained from then on)."""
    cd = jax.lax.all_gather(state["dcounters"], MESH_AXIS, axis=0, tiled=True)
    delta = cd.transpose(1, 0, 2).reshape(spec.counter_words)
    if spec.dk_bits:
        dd = jax.lax.all_gather(state["ddoorkeeper"], MESH_AXIS,
                                axis=0, tiled=True)
        ddk = dd.reshape(spec.dk_words)
    else:
        ddk = jnp.zeros_like(state["doorkeeper"])
    out = {k: v for k, v in state.items()
           if k not in ("dcounters", "ddoorkeeper")}
    out["counters"] = jnp.concatenate([state["counters"], delta])
    out["doorkeeper"] = jnp.concatenate([state["doorkeeper"], ddk])
    return out


def _split_delta_state(spec: StepSpec, state: dict, state0: dict) -> dict:
    """Inverse of :func:`_gather_delta_state` on exiting the program: slice
    this device's block of the (replicated) delta half back out so the
    returned pytree matches the mesh-layout partition specs.  ``state0`` is
    the device-local input state (for the dk_bits=0 placeholder, whose
    (local_shards, 1) block never reshapes from the flat layout)."""
    H, HD = spec.counter_words, spec.dk_words
    L = spec.local_shards
    base = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32) * L
    delta = state["counters"][H:].reshape(
        spec.rows, spec.shards, spec.wps_shard).transpose(1, 0, 2)
    out = {k: v for k, v in state.items()
           if k not in ("counters", "doorkeeper")}
    out["counters"] = state["counters"][:H]
    out["doorkeeper"] = state["doorkeeper"][:HD]
    out["dcounters"] = jax.lax.dynamic_slice(
        delta, (base, jnp.int32(0), jnp.int32(0)),
        (L, spec.rows, spec.wps_shard))
    if spec.dk_bits:
        ddk = state["doorkeeper"][HD:].reshape(spec.shards, spec.dkw_shard)
        out["ddoorkeeper"] = jax.lax.dynamic_slice(
            ddk, (base, jnp.int32(0)), (L, spec.dkw_shard))
    else:
        out["ddoorkeeper"] = state0["ddoorkeeper"]
    return out


def _mesh_runner(spec: StepSpec, mesh, adaptive: bool):
    """One compiled multi-device program: a shard_map over the ("shard",)
    mesh whose body is the epoch-chunked scan — full (unmasked) merge
    epochs inside the scan, the (< merge_every) tail as a plain step after
    it, exactly like the single-device jit backend.  NO per-access
    collective in either exchange mode (``StepSpec.mesh_exchange``):

    * ``"chunk"``: :func:`_gather_delta_state` on entry, then every device
      replays the identical single-device program (``mesh_devices=0``
      spec) over its replicated [global || delta] replica — step scan +
      ``merge_halve`` fold, zero collectives — and
      :func:`_split_delta_state` restores the mesh layout on exit.
      Bit-identical to the single-device sharded run by construction.
    * ``"stale"``: the mesh layout is kept throughout — per-access delta
      writes stay device-local, estimates read the (<= one epoch stale)
      replicated global halves only, and the per-epoch
      ``merge_halve_mesh`` all-gather fold is the one collective.

    Every device computes identical replicated verdicts over the
    replicated cache tables; only its local delta blocks differ."""
    key = (spec, mesh, adaptive)
    if key not in _mesh_cache:
        if len(_mesh_cache) >= _MESH_CACHE_LIMIT:
            _mesh_cache.clear()
        from jax.sharding import PartitionSpec as P
        sspec = _mesh_state_specs(spec)
        chunked = spec.mesh_exchange == "chunk"
        # chunk mode replays the single-device program — same geometry,
        # single-device state layout — inside the shard_map body
        lspec = replace(spec, mesh_devices=0) if chunked else spec

        def enter(state):
            return _gather_delta_state(spec, state) if chunked else state

        def leave(st, state0):
            return _split_delta_state(spec, st, state0) if chunked else st

        def fold(params, st):
            return (merge_halve(lspec, params, st) if chunked
                    else merge_halve_mesh(spec, params, st))

        if not adaptive:
            def fn(params, state, los, his, tlo, thi):
                st0 = enter(state)

                def body(s, x):
                    clo, chi = x
                    s, hits = step_ref(lspec, params, s, clo, chi)
                    return fold(params, s), hits
                st, hits = jax.lax.scan(body, st0, (los, his))
                st, tail = step_ref(lspec, params, st, tlo, thi)
                return leave(st, state), jnp.concatenate(
                    [hits.reshape(-1), tail])

            _mesh_cache[key] = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(P(), sspec, P(), P(), P(), P()),
                out_specs=(sspec, P()), check_vma=False))
        else:
            def fn(params, state, los, his, tlo, thi, climb, carry0):
                st0 = enter(state)

                def body(carry, x):
                    clo, chi = x
                    s = carry[0]
                    s, hits = step_ref(lspec, params, s, clo, chi)
                    ehits = s["regs"][R_EHITS]
                    quota = s["regs"][R_WQUOTA]
                    # merge rides the climb epochs: fold first, then climb
                    # + rebalance — same order as the single-device runner
                    sm = fold(params, s)
                    carry = _climb_step(params, lspec, (sm,) + carry[1:],
                                        ehits, climb)
                    return carry, (hits, ehits, quota)

                init = (st0, carry0[0], carry0[1], carry0[2],
                        carry0[3], carry0[4], carry0[5])
                (st, *regs), (hits, ehits, quotas) = jax.lax.scan(
                    body, init, (los, his))
                st, tail = step_ref(lspec, params, st, tlo, thi)
                return (leave(st, state),
                        jnp.concatenate([hits.reshape(-1), tail]),
                        ehits, quotas, jnp.stack(regs))

            _mesh_cache[key] = jax.jit(jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P(), sspec, P(), P(), P(), P(), P(), P()),
                out_specs=(sspec, P(), P(), P(), P()), check_vma=False))
    return _mesh_cache[key]


def _pad_epochs(lo, hi, n: int, E: int):
    """Pad the trace to whole epochs; returns (los, his, nvalid) chunked.
    Lane-batched traces (leading (B,) axis) pad/chunk along the access
    axis; nvalid stays per-epoch scalar — lanes share the chunking."""
    pad = (-n) % E
    if pad:
        z = jnp.zeros(lo.shape[:-1] + (pad,), lo.dtype)
        lo = jnp.concatenate([lo, z], axis=-1)
        hi = jnp.concatenate([hi, z], axis=-1)
    ne = lo.shape[-1] // E
    nvalid = jnp.minimum(
        jnp.maximum(n - jnp.arange(ne, dtype=jnp.int32) * E, 0), E)
    return _chunk_lanes(lo, ne, E), _chunk_lanes(hi, ne, E), nvalid


def _sharded_runner(spec: StepSpec, backend: str, interpret: bool):
    """One compiled program: scan over merge epochs, each epoch = fused step
    over its chunk + merge_halve fold.  No host sync anywhere inside the
    trace — the sharded twin of ``_adaptive_runner`` without the climb."""
    key = (spec, backend, interpret)
    if key not in _sharded_cache:
        if len(_sharded_cache) >= _STEP_CACHE_LIMIT:
            _sharded_cache.clear()
        @jax.jit
        def run(params, state, los, his, nvalid):
            def body(st, x):
                clo, chi, nv = x
                if backend == "pallas":
                    st, hits = step_pallas(spec, params, st, clo, chi, nv,
                                           interpret=interpret)
                else:
                    st, hits = step_ref(spec, params, st, clo, chi)
                # a partial (padded tail) epoch does not merge — the jit
                # backend runs the tail outside the scan without a merge,
                # and the two must agree on the final state.  The gate
                # touches ONLY the sketch arrays the fold modifies: a
                # whole-state tree_map would copy the cache tables every
                # epoch, which at large capacities dwarfs the per-access
                # work and sinks the flatness arm (measured 4x at C=65536)
                merged = merge_halve(spec, params, st)
                full = nv >= jnp.int32(clo.shape[-1])
                gated = ("counters", "doorkeeper", "regs") + \
                    (("csum",) if spec.integrity else ())
                st = {**st, **{k: jnp.where(full, merged[k], st[k])
                               for k in gated}}
                return st, hits
            return jax.lax.scan(body, state, (los, his, nvalid))
        _sharded_cache[key] = run
    return _sharded_cache[key]


def _run_sharded(spec: StepSpec, params, state, lo, hi, merge_every: int,
                 backend: str, interpret: bool, mesh=None):
    """Merge-epoch-chunked sharded simulation; returns (state, hits).

    The jit backend scans whole epochs (each followed by the merge_halve
    fold) and runs the (< merge_every) tail as one extra dispatch without a
    final merge; the pallas backend folds the tail into a masked final
    epoch whose merge is skipped.  Both emit identical per-access hit flags
    and final state — and both match the host twin, which merges after
    every ``merge_every``-th access and never on a partial tail.

    ``mesh`` selects the multi-device shard_map runner — exact
    ("chunk") or speculative stale-global ("stale") exchange per
    ``spec.mesh_exchange``, both collective-free on the per-access path;
    it chunks the trace exactly like the jit backend (whole epochs in the
    scan, tail outside without a merge), so chunk mode's hits and final
    state are bit-identical to both single-device backends.

    ``spec.streams > 1``: lo/hi are (B, T) lane traces; epochs chunk along
    the access axis and hits come back (B, T) — lanes never interact, the
    per-lane fold is the vmapped single-stream ``merge_halve``.
    """
    n = lo.shape[-1]
    E = int(merge_every)
    if mesh is not None:
        ne = n // E
        nfull = ne * E
        state, hits = _mesh_runner(spec, mesh, False)(
            params, state, lo[:nfull].reshape(ne, E),
            hi[:nfull].reshape(ne, E), lo[nfull:], hi[nfull:])
        return state, hits
    if backend == "pallas":
        los, his, nvalid = _pad_epochs(lo, hi, n, E)
        state, hits = _sharded_runner(spec, backend, interpret)(
            params, state, los, his, nvalid)
        if spec.streams > 1:                 # (ne, B, E) -> (B, T)
            return state, hits.swapaxes(0, 1).reshape(spec.streams, -1)[:, :n]
        return state, hits.reshape(-1)[:n]
    ne = n // E
    nfull = ne * E
    B = spec.streams
    hits_parts = []
    if ne:
        state, hits = _sharded_runner(spec, backend, interpret)(
            params, state, _chunk_lanes(lo[..., :nfull], ne, E),
            _chunk_lanes(hi[..., :nfull], ne, E),
            jnp.full((ne,), E, jnp.int32))
        hits_parts.append(hits.swapaxes(0, 1).reshape(B, nfull)
                          if B > 1 else hits.reshape(-1))
    if n - nfull:
        state, tail = _jit_step(spec, params, state, lo[..., nfull:],
                                hi[..., nfull:])
        hits_parts.append(tail)
    if not hits_parts:                       # zero-length trace
        hits_parts.append(jnp.zeros((B, 0) if B > 1 else (0,), jnp.int32))
    hits = jnp.concatenate(hits_parts, axis=-1) if len(hits_parts) > 1 \
        else hits_parts[0]
    return state, hits


# ---------------------------------------------------------------------------
# adaptive window sizing: epoch-chunked scan + in-program hill-climb
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ClimbSpec:
    """Hill-climber hyperparameters (resolved against a configuration).

    Every ``epoch_len`` accesses the compiled program compares the epoch's
    hit count with the previous epoch's: within ``tol`` counts as
    improvement (noise hysteresis) and keeps climbing in the same
    direction; a regression reverses direction and halves the step (floor
    1), so the quota converges toward the local optimum with decaying
    oscillation.  A swing larger than ``restart`` (either sign — the
    workload changed) re-expands the step to ``delta0`` so the climber can
    cross the quota range quickly after a phase shift.  The quota is
    clamped to [wmin, wmax].

    Field reference (zero fields auto-size — core/adaptive.py; rendered in
    docs/API.md):

    ``epoch_len`` (default 4096)
        Accesses per climb epoch.  Climb + rebalance (and, with
        ``shards>1``, the merge_halve fold) run at each epoch boundary
        inside the compiled program; partial tail epochs never climb.
    ``delta0`` (default 0 = auto ``wmax/16``)
        Initial quota step, and the step the phase-shift restart re-arms.
    ``wmin`` (default 1)
        Smallest quota the climb may set.
    ``wmax`` (default 0 = auto)
        Largest quota; auto = the adaptive table headroom
        (``window_max_frac`` of capacity — the static table sizing).
    ``tol`` (default 0 = auto ``epoch_len/256``)
        Noise hysteresis band (~0.4% hit-rate): epoch-hit deltas within
        ±tol are a plateau (hold position, decay the step).
    ``restart`` (default 0 = auto ``epoch_len/16``)
        Disruption threshold (~6% hit-rate swing vs the EWMA baseline);
        while tripped, improving moves double the step (capped at a
        quarter of the quota range).
    ``warm_epochs`` (default 3)
        Epochs that only seed the baselines — the fill-up transient
        swamps every signal.
    """
    epoch_len: int = 4096
    delta0: int = 0
    wmin: int = 1
    wmax: int = 0
    tol: int = 0
    restart: int = 0
    warm_epochs: int = 3

    def resolve(self, cfg: "DeviceWTinyLFU") -> np.ndarray:
        return np.asarray(
            adaptive.resolve_climb(self.epoch_len, self.delta0, self.wmin,
                                   self.wmax, self.tol, self.restart,
                                   self.warm_epochs, cfg.window_cap_max),
            np.int32)


def _climb_step(params, spec, carry, ehits, climb):
    """One hill-climb update + rebalance (pure jnp, runs between epochs).

    Three-way comparison against the previous epoch: a real improvement
    (> tol) keeps direction and step; a real regression (< -tol) reverses
    and halves the step; the noise plateau in between keeps direction but
    decays the step 3/4 so a flat hit-ratio landscape freezes the quota
    instead of letting it drift.  A swing beyond ``restart`` (the workload
    changed) re-expands the step to delta0.  The first epoch only seeds the
    baseline — the cache is still warming, and climbing on the fill-up
    transient launches the quota far from any optimum.

    ``spec.streams > 1``: every climber register is per-lane (the carry
    scalars become (B,) rows of the (6, B) carry matrix) and the update
    vmaps over lanes, so B tenants hill-climb independently inside one
    program.  ``climb`` may be shared (6,) or per-lane (B, 6) — the latter
    is how ``simulate_sweep(mode="vmap", adaptive=True)`` runs climber
    hyperparameter grids as lanes.
    """
    if spec.streams > 1:
        lspec = replace(spec, streams=1)
        cvec = jnp.asarray(climb)

        def one(p, cv, st, prev, dirn, delta, ewma, trend, k, eh):
            return _climb_step(p, lspec,
                               (st, prev, dirn, delta, ewma, trend, k),
                               eh, cv)
        return jax.vmap(one, in_axes=(0 if params.ndim == 2 else None,
                                      0 if cvec.ndim == 2 else None)
                        + (0,) * 8)(params, cvec, *carry, ehits)
    st, prev, dirn, delta, ewma, trend, k = carry
    quota = st["regs"][R_WQUOTA]
    diff = ehits - prev
    # trend correction: judge a move against the background drift (EWMA of
    # recent diffs), not against zero — a cache still warming up improves
    # every epoch no matter what the quota does, and crediting that drift
    # to the last move rides the quota far from any optimum
    adiff = diff - trend
    improved = adiff > climb[3]
    regressed = adiff < -climb[3]
    trend_n = jnp.where(prev < 0, 0, trend + (diff - trend) // 4)
    dirn_n = jnp.where(regressed, -dirn, dirn)
    delta_n = jnp.where(regressed, jnp.maximum(delta // 2, 1),
                        jnp.where(improved, delta,
                                  jnp.maximum((delta * 3) // 4, 1)))
    # disruption restart: while the epoch hit count sits far from its
    # recent average (phase shift, or mid-recovery after one) the step must
    # stay wide — consecutive-epoch diffs alone go quiet as soon as the
    # collapse settles, long before the quota has crossed back to useful
    # territory, and a decayed step would crawl there at +-1 per epoch.
    # While the disruption lasts, improving moves double the step (capped
    # at a quarter of the quota range) so the recovery crosses the range in
    # a handful of epochs; non-improving ones reset it to delta0
    shift = jnp.abs(ehits - ewma) > climb[4]
    span4 = jnp.maximum(climb[0], (climb[2] - climb[1]) // 4)
    delta_n = jnp.where(
        shift,
        jnp.where(improved,
                  jnp.minimum(jnp.maximum(delta_n, climb[0]) * 2, span4),
                  climb[0]),
        delta_n)
    # warm epochs: the fill-up transient swamps every signal (its epoch
    # diffs trip even the disruption detector) — hold the quota and step,
    # and let the baselines FOLLOW the transient (ewma = ehits, trend =
    # diff) so the handoff into live climbing starts from honest levels
    # instead of a lagging average that reads as a disruption
    warm = k < climb[5]
    ewma = jnp.where(warm | (prev < 0), ehits,
                     ewma + (ehits - ewma) // 4)
    dirn = jnp.where(warm, dirn, dirn_n)
    delta = jnp.where(warm, delta, delta_n)
    trend = jnp.where(warm, jnp.where(prev < 0, 0, diff), trend_n)
    # a plateau decays the step but does NOT move: drifting at the decaying
    # step across a shallow landscape accumulates several delta0 of
    # displacement before freezing.  Disruptions always move — during a
    # recovery the trend estimate absorbs the climb's own gains, and
    # holding still there would stall the recovery mid-range.
    move = improved | regressed | shift
    step = jnp.where(warm | ~move, 0, dirn * delta)
    nq = jnp.clip(quota + step, climb[1], climb[2])
    # clamp escape: pinned at a range end with a flat (possibly uniformly
    # terrible) hit landscape there is no regression signal to reverse on —
    # point the next step back into the range
    dirn = jnp.where(nq <= climb[1], 1,
                     jnp.where(nq >= climb[2], -1, dirn))
    st = rebalance(spec, params, st, nq)
    return st, ehits, dirn, delta, ewma, trend, k + 1


_adaptive_cache: dict = {}


def _adaptive_runner(spec: StepSpec, backend: str, interpret: bool):
    """One compiled program: scan over epochs, each epoch = fused step over
    its chunk + climb + rebalance.  No host sync anywhere inside the trace."""
    key = (spec, backend, interpret)
    if key not in _adaptive_cache:
        if len(_adaptive_cache) >= _STEP_CACHE_LIMIT:
            _adaptive_cache.clear()
        @jax.jit
        def run(params, state, los, his, nvalid, climb, carry0):
            def body(carry, x):
                clo, chi, nv = x
                st = carry[0]
                if backend == "pallas":
                    st, hits = step_pallas(spec, params, st, clo, chi, nv,
                                           interpret=interpret)
                else:
                    st, hits = step_ref(spec, params, st, clo, chi)
                # [..., R] keeps the epoch registers per-lane under streams
                # (regs is (B, NREGS) there, (NREGS,) unbatched)
                ehits = st["regs"][..., R_EHITS]
                quota = st["regs"][..., R_WQUOTA]
                # sharded + adaptive: the merge_halve fold rides the climb
                # epochs (merge first, then climb + rebalance — the host
                # twin AdaptiveWTinyLFU merges at the same point); the
                # `full` gate below skips both on a padded partial tail
                stm = merge_halve(spec, params, st) if spec.shards > 1 else st
                climbed = _climb_step(params, spec, (stm,) + carry[1:],
                                      ehits, climb)
                # a partial (padded tail) epoch must not climb: its truncated
                # hit count reads as a phase shift, and the jit backend —
                # which runs the tail outside the scan — would disagree on
                # final quota and state
                full = nv >= jnp.int32(clo.shape[-1])
                carry = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(full, a, b), climbed,
                    (st,) + carry[1:])
                return carry, (hits, ehits, quota)

            # the climber's scalar registers enter/leave as a (6,) int32
            # vector [prev, dirn, delta, ewma, trend, k] so a checkpointed
            # run can hand them across segment boundaries bit-exactly
            init = (state, carry0[0], carry0[1], carry0[2],
                    carry0[3], carry0[4], carry0[5])
            (st, *regs), (hits, ehits, quotas) = jax.lax.scan(
                body, init, (los, his, nvalid))
            return st, hits, ehits, quotas, jnp.stack(regs)
        _adaptive_cache[key] = run
    return _adaptive_cache[key]


def _climb_carry0(cvec) -> jnp.ndarray:
    """Fresh-run climber registers: [prev=-1, dirn=1, delta=delta0,
    ewma=-1, trend=0, k=0] — the pre-ISSUE-7 scan init, as a vector."""
    return jnp.stack([jnp.int32(-1), jnp.int32(1),
                      jnp.asarray(cvec[0], jnp.int32), jnp.int32(-1),
                      jnp.int32(0), jnp.int32(0)])


def _run_adaptive(cfg: "DeviceWTinyLFU", spec: StepSpec, params, state,
                  lo, hi, climb: ClimbSpec, backend: str, interpret: bool,
                  mesh=None, carry=None):
    """Epoch-chunked adaptive simulation; returns (state, hits, trajectory,
    carry) where ``carry`` is the (6,) int32 climber-register vector.

    The jit backend scans whole epochs and runs the (< epoch_len) tail as
    one extra dispatch without a final climb; the pallas backend folds the
    tail into a masked final epoch whose climb is skipped.  Both emit
    identical per-access hit flags, final quota, and trajectory (full
    epochs only).  ``mesh`` selects the multi-device shard_map runner
    (whole epochs in the scan, tail outside without a climb, like jit) —
    the merge fold rides the climb epochs.

    ``carry=None`` starts a fresh climb; a checkpointed run passes the
    previous segment's carry so that splitting the trace at epoch
    boundaries reproduces the single-program run bit-for-bit.

    ``spec.streams > 1``: lo/hi are (B, T) lane traces, the carry is the
    (6, B) per-lane climber-register matrix, and the trajectory rows are
    per-lane ``(ne, B)`` — B independent hill-climbs in one program.
    """
    n = lo.shape[-1]
    E = int(climb.epoch_len)
    cvec = jnp.asarray(climb.resolve(cfg))
    if carry is None:
        carry = _climb_carry0(cvec)
        if spec.streams > 1:
            carry = jnp.repeat(carry[:, None], spec.streams, axis=1)
    if mesh is not None:
        ne = n // E
        nfull = ne * E
        state, hits, ehits, quotas, carry = _mesh_runner(spec, mesh, True)(
            params, state, lo[:nfull].reshape(ne, E),
            hi[:nfull].reshape(ne, E), lo[nfull:], hi[nfull:], cvec, carry)
        traj = (ehits, quotas) if ne else (None, None)
        return state, hits, traj, carry
    B = spec.streams
    if backend == "pallas":
        los, his, nvalid = _pad_epochs(lo, hi, n, E)
        state, hits, ehits, quotas, carry = _adaptive_runner(
            spec, backend, interpret)(params, state, los, his, nvalid, cvec,
                                      carry)
        nfull = n // E                   # drop the partial tail's row so the
        traj = (ehits[:nfull], quotas[:nfull]) if nfull else (None, None)
        hits = (hits.swapaxes(0, 1).reshape(B, -1)[:, :n] if B > 1
                else hits.reshape(-1)[:n])
        return state, hits, traj, carry  # traj matches jit
    ne = n // E
    nfull = ne * E
    hits_parts = []
    ehits = quotas = None
    if ne:
        state, hits, ehits, quotas, carry = _adaptive_runner(
            spec, backend, interpret)(params, state,
                                      _chunk_lanes(lo[..., :nfull], ne, E),
                                      _chunk_lanes(hi[..., :nfull], ne, E),
                                      jnp.full((ne,), E, jnp.int32), cvec,
                                      carry)
        hits_parts.append(hits.swapaxes(0, 1).reshape(B, nfull)
                          if B > 1 else hits.reshape(-1))
    if n - nfull:
        state, tail = _jit_step(spec, params, state, lo[..., nfull:],
                                hi[..., nfull:])
        hits_parts.append(tail)
    if not hits_parts:                       # zero-length trace
        hits_parts.append(jnp.zeros((B, 0) if B > 1 else (0,), jnp.int32))
    hits = jnp.concatenate(hits_parts, axis=-1) if len(hits_parts) > 1 \
        else hits_parts[0]
    return state, hits, (ehits, quotas), carry


def _policy_label(cfg: "DeviceWTinyLFU", adaptive: bool) -> str:
    """SimResult.policy label.  The W-TinyLFU spelling predates the policy
    panel and is pinned by downstream plot/golden tooling, so it is kept
    verbatim; competitors label as ``"<policy>(device)"``."""
    base = ("w-tinylfu(device)" if cfg.policy == "wtinylfu"
            else f"{cfg.policy}(device)")
    return base + ("+climb" if adaptive else "")


def _row_extra(cfg: "DeviceWTinyLFU", climb: "ClimbSpec | None",
               adaptive: bool) -> dict:
    """Config-knob rows shared by every ``SimResult.extra`` the engine
    emits — ``simulate_trace``, ``run()``, and each ``simulate_sweep`` row
    build on this one dict so the row schema cannot drift (sweep rows used
    to silently omit ``streams``/``integrity``/``merge_every``).  Knobs at
    their defaults stay absent so pre-existing row shapes are unchanged."""
    extra = {}
    if cfg.policy != "wtinylfu":
        extra["policy"] = cfg.policy
    if cfg.mesh is not None:
        extra["mesh_devices"] = cfg.mesh_devices
        extra["mesh_exchange"] = cfg.mesh_exchange
    if cfg.shards > 1:
        extra["shards"] = cfg.shards
        # adaptive+sharded: the fold rides the climb epochs, not merge_epoch
        extra["merge_every"] = (climb.epoch_len if adaptive and climb
                                else cfg.merge_epoch)
    if cfg.integrity:
        extra["integrity"] = True
    if cfg.streams > 1:
        extra["streams"] = cfg.streams
    return extra


def _span(phase: str):
    """Host span ``simulate_trace.<phase>``: recorded on the host plane of
    a profiler session, on the device ops' clock; ~1 us with no session."""
    return jax.profiler.TraceAnnotation("simulate_trace." + phase)


def _event_counts(regs: np.ndarray) -> dict:
    """The admission event registers of ``StepSpec.events``, summed over
    tenant lanes: ``candidates`` (window overflows that pushed one),
    ``admitted`` and ``rejected`` (a candidate's estimate against a
    resident victim's: replaced it, or lost), ``resets`` (§3.3)."""
    regs = regs.reshape(-1, regs.shape[-1])
    return {name: int(regs[:, r].sum()) for name, r in (
        ("candidates", R_CANDS), ("admitted", R_ADMIT),
        ("rejected", R_REJECT), ("resets", R_RESETS))}


def simulate_trace(trace: np.ndarray, capacity: int, *,
                   window_frac: float = 0.01, sample_factor: int = 8,
                   warmup: int = 0, backend: str = "jit", chunk: int = 512,
                   interpret: bool | None = None, trace_name: str = "?",
                   return_state: bool = False, adaptive: bool = False,
                   climb: ClimbSpec | None = None, **cfg_kw) -> SimResult:
    """Device twin of ``simulate.run_trace(WTinyLFU(capacity), trace)``.

    ``backend="jit"`` runs the scan twin; ``backend="pallas"`` launches the
    fused kernel per chunk (interpret mode anywhere off-TPU).  ``warmup``
    accesses update state but are not counted, exactly like ``run_trace``.
    ``assoc=W`` (via cfg_kw) selects the W-way set-associative tables —
    O(W) per access instead of O(capacity), hit ratios within ±0.01 of the
    exact path; ``counter_bits=8`` enables sample factors above 16.

    ``adaptive=True`` makes the window/main split runtime device state: an
    epoch-based hill-climber (``climb``, default :class:`ClimbSpec`) adjusts
    the window quota between epochs inside the same compiled program, and
    the per-epoch (quota, hits) trajectory is returned in
    ``extra["trajectory"]``.  ``window_frac`` seeds the initial quota.

    ``shards=S`` (via cfg_kw) runs the sharded frequency sketch: the trace
    is chunked into merge epochs (``merge_every`` accesses, 0 = auto) and a
    fused ``merge_halve`` folds the shard deltas into the global estimate
    at every boundary — combined with ``adaptive=True`` the fold rides the
    climb epochs instead.

    ``events=True`` (via cfg_kw; see :class:`DeviceWTinyLFU`) returns the
    run's admission counts as ``extra["events"]``: ``candidates``,
    ``admitted``, ``rejected`` and ``resets``, summed over tenant lanes.

    Each host phase runs under a ``jax.profiler.TraceAnnotation`` named
    ``simulate_trace.<phase>`` (``config``, ``init_state``,
    ``stage_keys``, ``dispatch``, ``wait``, ``readback``), so a profile
    of a replay names the host's work on the device ops' clock.
    """
    with _span("config"):
        cfg = DeviceWTinyLFU(capacity, window_frac=window_frac,
                             sample_factor=sample_factor, adaptive=adaptive,
                             **cfg_kw)
        trace = np.asarray(trace)
        _check_trace_streams(cfg, trace)
        spec = cfg.spec()
        params = cfg.params(warmup=warmup)
    with _span("init_state"):
        state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
    with _span("stage_keys"):
        lo, hi = _trace_lanes(trace)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    climb = climb or ClimbSpec()

    if cfg.mesh is not None and backend != "jit":
        raise ValueError("mesh execution runs the jit scan under shard_map: "
                         "use backend='jit'")
    t0 = time.perf_counter()
    trajectory = None
    with _span("dispatch"):
        if adaptive:
            if backend not in ("jit", "pallas"):
                raise ValueError(f"unknown backend {backend!r}")
            state, hits, (ehits, quotas), _ = _run_adaptive(
                cfg, spec, params, state, lo, hi, climb, backend, interpret,
                mesh=cfg.mesh)
            if ehits is not None:
                trajectory = {"epoch_len": climb.epoch_len,
                              "epoch_hits": np.asarray(ehits).tolist(),
                              "quota": np.asarray(quotas).tolist()}
        elif cfg.shards > 1:
            if backend not in ("jit", "pallas"):
                raise ValueError(f"unknown backend {backend!r}")
            state, hits = _run_sharded(spec, params, state, lo, hi,
                                       cfg.merge_epoch, backend, interpret,
                                       mesh=cfg.mesh)
        elif backend == "jit":
            state, hits = _run_jit(spec, params, state, lo, hi)
        elif backend == "pallas":
            state, hits = _run_pallas(spec, params, state, lo, hi, chunk,
                                      interpret)
        else:
            raise ValueError(f"unknown backend {backend!r}")
        if cfg.mesh is not None:
            # hand back the single-device [global || delta] layout so callers
            # compare final sketch state across placements directly
            state = _from_mesh_state(spec, state)
    with _span("wait"):
        jax.block_until_ready(state["regs"])
    with _span("readback"):
        regs = np.asarray(state["regs"])
        wall = time.perf_counter() - t0

        # warmup applies per lane (each tenant's own R_T register counts it)
        counted = (trace.shape[-1] - warmup) * cfg.streams
        extra = {"backend": backend, "window_frac": window_frac,
                 "assoc": cfg.assoc, "device": jax.default_backend(),
                 **_row_extra(cfg, climb, adaptive)}
        if adaptive:
            extra["adaptive"] = True
            extra["final_quota"] = ([int(q) for q in regs[:, R_WQUOTA]]
                                    if cfg.streams > 1
                                    else int(regs[R_WQUOTA]))
            if trajectory is not None:
                extra["trajectory"] = trajectory
        if cfg.streams > 1:
            # aggregate hits in the SimResult; per-lane breakdown in extra
            # (trajectory rows are already per-lane (ne, B) lists)
            extra["lane_hits"] = [int(h) for h in regs[:, R_HITS]]
            n_hits = int(regs[:, R_HITS].sum())
        else:
            n_hits = int(regs[R_HITS])
        if cfg.events:
            extra["events"] = _event_counts(regs)
        res = SimResult(policy=_policy_label(cfg, adaptive),
                        cache_size=capacity,
                        trace=trace_name, accesses=counted, hits=n_hits,
                        hit_ratio=n_hits / max(1, counted),
                        wall_s=wall, extra=extra)
    if return_state:
        return res, state, hits
    return res


# ---------------------------------------------------------------------------
# fault-tolerant execution: epoch-boundary checkpoint / resume (ISSUE 7)
# ---------------------------------------------------------------------------

def _ckpt_epoch(cfg: "DeviceWTinyLFU", climb: ClimbSpec) -> int:
    """The run's state-handoff granularity in accesses.

    Adaptive runs climb (and, sharded, merge) every ``climb.epoch_len``;
    sharded static runs merge every ``merge_epoch``; a plain scan has no
    boundary constraint at all — any split is a clean handoff — so its
    epoch only sets the auto checkpoint cadence."""
    if cfg.adaptive:
        return int(climb.epoch_len)
    if cfg.shards > 1:
        return int(cfg.merge_epoch)
    return max(1, min(4096, cfg.sample_size))


def _resolve_every(cfg: "DeviceWTinyLFU", climb: ClimbSpec,
                   checkpoint_every: int) -> int:
    """Validated checkpoint cadence in accesses (0 = auto ~32k, rounded to
    whole epochs).  Epoch-chunked runs (adaptive / sharded) may only hand
    state off at epoch boundaries, so their cadence must be a multiple of
    the epoch — anything else could not reproduce the uninterrupted run."""
    E = _ckpt_epoch(cfg, climb)
    if checkpoint_every == 0:
        return E * max(1, 32768 // E)
    ce = int(checkpoint_every)
    chunked = cfg.adaptive or cfg.shards > 1
    if ce < 1 or (chunked and ce % E):
        kind = ("climb.epoch_len" if cfg.adaptive else
                "the resolved merge_epoch")
        raise ValueError(
            f"checkpoint_every {checkpoint_every} must be a positive "
            f"multiple of the run's epoch ({kind} = {E}): the engine "
            "hands state off only at epoch boundaries, so any other "
            "cadence cannot resume bit-identically")
    return ce


def _config_meta(cfg: "DeviceWTinyLFU", climb: ClimbSpec, warmup: int,
                 n: int) -> dict:
    """JSON-safe fingerprint of the logical run configuration, stored in
    every checkpoint's manifest and verified by :func:`resume_trace`.

    The mesh itself is deliberately ABSENT: placement is not part of the
    logical configuration, which is exactly what makes elastic restore
    (checkpoint on 2 devices, resume on 1, or vice versa) legal."""
    meta = {f: getattr(cfg, f) for f in (
        "capacity", "window_frac", "sample_factor", "protected_frac",
        "counters_per_item", "rows", "doorkeeper", "dk_bits_per_item",
        "assoc", "counter_bits", "adaptive", "window_max_frac", "shards",
        "merge_every", "integrity")}
    meta["mesh_exchange"] = (cfg.mesh_exchange if cfg.mesh is not None
                            else "chunk")
    if cfg.streams > 1:          # absent at 1 so pre-streams manifests match
        meta["streams"] = cfg.streams
    if cfg.policy != "wtinylfu":  # absent at default so old manifests match
        meta["policy"] = cfg.policy
    if cfg.events:               # absent when off: the regs keep 8 slots
        meta["events"] = True
    if cfg.adaptive:
        meta["climb"] = [int(x) for x in climb.resolve(cfg)]
    meta["warmup"] = int(warmup)
    meta["trace_len"] = int(n)
    return meta


def _segment(cfg: "DeviceWTinyLFU", spec: StepSpec, params, state, lo, hi,
             climb: ClimbSpec, carry, backend: str, chunk: int,
             interpret: bool):
    """One contiguous trace slice through the right runner; returns
    (state, hits, (ehits, quotas), carry)."""
    if cfg.adaptive:
        return _run_adaptive(cfg, spec, params, state, lo, hi, climb,
                             backend, interpret, mesh=cfg.mesh, carry=carry)
    if cfg.shards > 1:
        state, hits = _run_sharded(spec, params, state, lo, hi,
                                   cfg.merge_epoch, backend, interpret,
                                   mesh=cfg.mesh)
    elif backend == "jit":
        state, hits = _run_jit(spec, params, state, lo, hi)
    else:
        state, hits = _run_pallas(spec, params, state, lo, hi, chunk,
                                  interpret)
    return state, hits, (None, None), carry


def _run_checkpointed(cfg: "DeviceWTinyLFU", trace, *, warmup=0,
                      backend="jit", chunk=512, interpret=None,
                      trace_name="?", climb=None, checkpoint_dir=None,
                      checkpoint_every=0, return_state=False,
                      on_checkpoint=None, fault_hook=None,
                      _start=0, _state=None, _carry=None,
                      _hits_prefix=None, _traj_prefix=None):
    """Segmented engine driver behind :meth:`DeviceWTinyLFU.run` and
    :func:`resume_trace` (the leading-underscore kwargs are the resume
    handoff).  Every segment boundary is an epoch boundary, i.e. a clean
    state handoff, so the concatenated segments reproduce the
    single-program run bit-for-bit — hit sequence, climb trajectory, and
    final sketch words."""
    from repro.checkpoint.store import AsyncCheckpointer
    climb = climb or ClimbSpec()
    spec = cfg.spec()
    params = cfg.params(warmup=warmup)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if backend not in ("jit", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    if cfg.mesh is not None and backend != "jit":
        raise ValueError("mesh execution runs the jit scan under shard_map: "
                         "use backend='jit'")
    segmenting = checkpoint_dir is not None or fault_hook is not None
    if segmenting and backend != "jit":
        raise ValueError("checkpointing / fault injection segment the jit "
                         "scan: use backend='jit'")
    if segmenting and cfg.streams > 1:
        raise ValueError(
            f"streams {cfg.streams} does not combine with checkpoint_dir/"
            "fault_hook: the checkpoint tree and fault surface are the "
            "single-tenant state layout — run per-tenant streams=1 runs "
            "for fault-tolerant execution")
    _check_trace_streams(cfg, trace)
    lo, hi = _trace_lanes(trace)
    every = (_resolve_every(cfg, climb, checkpoint_every) if segmenting
             else None)
    n = lo.shape[-1]
    state = (_state if _state is not None
             else init_step_state(spec, cfg.window_cap, cfg.main_cap))
    carry = _carry
    ck = (AsyncCheckpointer(checkpoint_dir) if checkpoint_dir is not None
          else None)
    meta = _config_meta(cfg, climb, warmup, n)

    t0 = time.perf_counter()
    hits_parts = ([] if _hits_prefix is None
                  else [jnp.asarray(_hits_prefix)])
    ehits_parts, quota_parts = [], []
    if _traj_prefix is not None:
        ehits_parts.append(jnp.asarray(_traj_prefix[0]))
        quota_parts.append(jnp.asarray(_traj_prefix[1]))

    i = _start
    while True:
        j = n if every is None else min(n, i + every)
        if j > i:
            state, hits, (eh, qu), carry = _segment(
                cfg, spec, params, state, lo[..., i:j], hi[..., i:j],
                climb, carry, backend, chunk, interpret)
            hits_parts.append(hits)
            if eh is not None:
                ehits_parts.append(eh)
                quota_parts.append(qu)
        i = j
        if ck is not None:
            canon = (_from_mesh_state(spec, state) if cfg.mesh is not None
                     else state)
            tree = {"state": canon,
                    "carry": (carry if carry is not None
                              else jnp.zeros((6,), jnp.int32)),
                    "hits": (jnp.concatenate(hits_parts) if hits_parts
                             else jnp.zeros((0,), jnp.int32))}
            if cfg.adaptive:
                z = jnp.zeros((0,), jnp.int32)
                tree["ehits"] = (jnp.concatenate(ehits_parts)
                                 if ehits_parts else z)
                tree["quotas"] = (jnp.concatenate(quota_parts)
                                  if quota_parts else z)
            ck.save(int(i), tree, extra_meta={**meta, "cursor": int(i)})
            if on_checkpoint is not None:
                on_checkpoint(int(i))
        if i >= n:
            break
        if fault_hook is not None:
            # faults inject at the clean boundary, on the canonical layout
            # — the checkpoint just written holds the PRE-fault state
            canon = (_from_mesh_state(spec, state) if cfg.mesh is not None
                     else state)
            mutated = fault_hook(int(i), canon)
            if mutated is not None:
                state = (_to_mesh_state(spec, mutated)
                         if cfg.mesh is not None else mutated)
    if ck is not None:
        ck.wait()

    if cfg.mesh is not None:
        state = _from_mesh_state(spec, state)
    hits = (jnp.concatenate(hits_parts) if len(hits_parts) != 1
            else hits_parts[0]) if hits_parts else jnp.zeros((0,), jnp.int32)
    regs = np.asarray(state["regs"])
    wall = time.perf_counter() - t0

    counted = (n - warmup) * cfg.streams
    extra = {"backend": backend, "window_frac": cfg.window_frac,
             "assoc": cfg.assoc, "device": jax.default_backend(),
             **_row_extra(cfg, climb, cfg.adaptive)}
    if cfg.streams > 1:
        extra["lane_hits"] = [int(h) for h in regs[:, R_HITS]]
        n_hits = int(regs[:, R_HITS].sum())
    else:
        n_hits = int(regs[R_HITS])
    if cfg.adaptive:
        extra["adaptive"] = True
        extra["final_quota"] = ([int(q) for q in regs[:, R_WQUOTA]]
                                if cfg.streams > 1 else int(regs[R_WQUOTA]))
        if ehits_parts:
            ehits = np.asarray(jnp.concatenate(ehits_parts))
            quotas = np.asarray(jnp.concatenate(quota_parts))
            extra["trajectory"] = {"epoch_len": climb.epoch_len,
                                   "epoch_hits": ehits.tolist(),
                                   "quota": quotas.tolist()}
    if cfg.events:
        extra["events"] = _event_counts(regs)
    if checkpoint_dir is not None:
        extra["checkpoint_every"] = every
    if _start:
        extra["resumed_at"] = int(_start)
    res = SimResult(policy=_policy_label(cfg, cfg.adaptive),
                    cache_size=cfg.capacity, trace=trace_name,
                    accesses=counted, hits=n_hits,
                    hit_ratio=n_hits / max(1, counted),
                    wall_s=wall, extra=extra)
    if return_state:
        return res, state, hits
    return res


def resume_trace(trace, cfg: DeviceWTinyLFU, *, checkpoint_dir: str,
                 warmup: int = 0, backend: str = "jit", chunk: int = 512,
                 interpret: bool | None = None, trace_name: str = "?",
                 climb: ClimbSpec | None = None, checkpoint_every: int = 0,
                 return_state: bool = False, on_checkpoint=None,
                 fault_hook=None):
    """Restore the latest complete checkpoint in ``checkpoint_dir`` and
    finish the run; bit-identical to the uninterrupted
    ``cfg.run(trace, checkpoint_dir=...)`` (hit sequence, trajectory, final
    sketch words).

    Checkpoints store the CANONICAL single-device state layout, so restore
    is elastic: a snapshot written by a 2-device mesh run resumes on a
    single device (or any mesh whose size divides ``cfg.shards``) — the
    delta blocks re-shard through ``checkpoint.store.restore_checkpoint``
    + ``distributed.mesh.mesh_state_shardings``.  With no checkpoint yet
    (killed before the first snapshot), the resume IS a fresh run.  A
    checkpoint written under a different logical configuration (any
    ``DeviceWTinyLFU`` field, climb vector, warmup, or trace length) is
    rejected with ``ValueError`` rather than silently continued.
    """
    from repro.checkpoint.store import (latest_step, load_meta,
                                        restore_checkpoint)
    climb = climb or ClimbSpec()
    common = dict(warmup=warmup, backend=backend, chunk=chunk,
                  interpret=interpret, trace_name=trace_name, climb=climb,
                  checkpoint_dir=checkpoint_dir,
                  checkpoint_every=checkpoint_every,
                  return_state=return_state, on_checkpoint=on_checkpoint,
                  fault_hook=fault_hook)
    step = latest_step(checkpoint_dir)
    if step is None:
        out = _run_checkpointed(cfg, trace, **common)
        (out[0] if return_state else out).extra["resumed_at"] = 0
        return out
    meta = dict(load_meta(checkpoint_dir, step))
    cursor = int(meta.pop("cursor", step))
    expect = _config_meta(cfg, climb, warmup, len(trace))
    if meta != expect:
        diffs = sorted(k for k in set(meta) | set(expect)
                       if meta.get(k) != expect.get(k))
        raise ValueError(
            f"checkpoint {checkpoint_dir!r} step {step} was saved under a "
            f"different configuration (differing fields: {diffs}) — resume "
            "with the original DeviceWTinyLFU / climb / warmup / trace")
    spec = cfg.spec()
    cspec = replace(spec, mesh_devices=0) if cfg.mesh is not None else spec
    template = {"state": init_step_state(cspec, cfg.window_cap,
                                         cfg.main_cap),
                "carry": jnp.zeros((6,), jnp.int32),
                "hits": jnp.zeros((cursor,), jnp.int32)}
    if cfg.adaptive:
        ne = cursor // int(climb.epoch_len)
        template["ehits"] = jnp.zeros((ne,), jnp.int32)
        template["quotas"] = jnp.zeros((ne,), jnp.int32)
    tree = restore_checkpoint(checkpoint_dir, step, template)
    state = tree["state"]
    if cfg.mesh is not None:
        from repro.distributed.mesh import mesh_state_shardings
        state = _to_mesh_state(spec, state)
        sh = mesh_state_shardings(cfg.mesh, state.keys())
        state = {k: jax.device_put(v, sh[k]) for k, v in state.items()}
    return _run_checkpointed(
        cfg, trace, _start=cursor, _state=state,
        _carry=(tree["carry"] if cfg.adaptive else None),
        _hits_prefix=tree["hits"],
        _traj_prefix=((tree["ehits"], tree["quotas"]) if cfg.adaptive
                      else None),
        **common)


# ---------------------------------------------------------------------------
# vmapped multi-configuration sweeps: one compiled program per grid
# ---------------------------------------------------------------------------

def simulate_sweep(trace: np.ndarray, capacities, *, window_fracs=(0.01,),
                   sample_factor: int = 8, warmup: int = 0,
                   trace_name: str = "?", verbose: bool = False,
                   mode: str = "auto", adaptive: bool = False,
                   climb: ClimbSpec | None = None,
                   policies=("wtinylfu",), **cfg_kw) -> list[SimResult]:
    """Cartesian (capacity × window_frac × policy) sweep.

    All configurations share the static geometry of the *largest* one (table
    slots are padded up; smaller capacities mark the excess slots as padding),
    so ONE compiled step program serves the whole grid; the sketch of a
    smaller configuration is sized for the largest sample — its estimates are
    slightly *more* accurate than a per-size host sketch, which is within the
    golden tolerance.

    ``mode``: ``"vmap"`` runs the whole grid as a single vmapped scan (the
    shape intended for accelerators — grid points ride the vector lanes; all
    configs share the largest config's sketch geometry); ``"sequential"``
    runs one compiled single-config scan per grid point with each config's
    own host-matched sketch sizing (faster on CPU, where XLA's batching
    rules serialize the lanes anyway, and directly comparable to per-size
    host results); ``"auto"`` picks vmap on TPU and sequential elsewhere.

    ``trace`` may be ``(N,)`` (shared by all configs) or ``(G, N)`` (one
    trace per grid point, e.g. seed sweeps).

    ``adaptive=True`` runs the in-program hill-climber per grid point
    (``window_fracs`` seed the initial quotas).  ``mode="sequential"``
    runs one epoch-chunked compiled program per config;
    ``mode="vmap"`` runs the whole grid as tenant LANES of ONE
    ``streams=len(grid)`` compiled program (``StepSpec.streams``) —
    per-lane quota and climber registers keep every grid point's history
    independent, bit-identical to the sequential runs.  The lanes share
    one static geometry, so vmapped adaptive grids may vary
    ``window_fracs`` and climb hyperparameters but not capacity/sizing.
    ``climb`` may be one ``ClimbSpec`` for the whole grid or a sequence of
    ``len(grid)`` specs (uniform ``epoch_len`` — the lanes climb in
    lockstep), which is how climber hyperparameter grids sweep as lanes.

    ``policies=`` adds the device policy-panel axis (kernels
    ``StepSpec.policy``: ``"wtinylfu" | "s3fifo" | "arc" | "lfu"``) to the
    grid.  Policy dispatch is *static* — each policy traces a different
    step program — so multi-policy grids run ``mode="sequential"``; a grid
    restricted to one policy may still vmap.  Competitor policies require
    ``assoc=`` (see :class:`DeviceWTinyLFU`).
    """
    if cfg_kw.get("events"):
        raise ValueError("events=True counts one run's admissions: use "
                         "simulate_trace per configuration (sweep rows "
                         "do not carry the counts)")
    policies = tuple(policies)
    grid = [DeviceWTinyLFU(C, window_frac=wf, sample_factor=sample_factor,
                           adaptive=adaptive, policy=pol, **cfg_kw)
            for C in capacities for wf in window_fracs for pol in policies]
    gridlab = [(C, wf) for C in capacities for wf in window_fracs
               for pol in policies]
    if len(set(policies)) > 1 and mode == "vmap":
        raise ValueError(
            "policy grids run one compiled step program per policy (the "
            "dispatch is static, traced into the program): use "
            "mode='sequential'")
    if len(set(policies)) > 1 and mode == "auto":
        mode = "sequential"
    sharded = any(c.shards > 1 for c in grid)
    meshed = any(c.mesh is not None for c in grid)
    if meshed:
        for c in grid:
            c.mesh_devices    # eager: reject bad mesh/shards combos up front
    if mode == "auto":
        # sharded/meshed grids can't share geometry (merge epochs need the
        # epoch-chunked runner; mesh runs need the shard_map runner), and
        # adaptive grids usually sweep capacities (distinct geometries), so
        # auto resolves to the always-valid mode even on accelerators;
        # adaptive same-geometry grids opt into lanes with mode="vmap"
        mode = "sequential" if (adaptive or sharded or meshed) else (
            "vmap" if jax.default_backend() == "tpu" else "sequential")
    if adaptive:
        climb = climb or ClimbSpec()
        climbs = (list(climb) if isinstance(climb, (list, tuple))
                  else [climb] * len(grid))
        if len(climbs) != len(grid):
            raise ValueError(f"climb sequence length {len(climbs)} != "
                             f"{len(grid)} grid configurations")
    if meshed and mode == "vmap":
        raise ValueError("mesh sweeps run per-config shard_map programs "
                         "(the vmapped scan would silently run the "
                         "single-device path): use mode='sequential'")
    if sharded and mode == "vmap":
        raise ValueError("sharded sweeps run per-config epoch-chunked "
                         "programs: use mode='sequential'")

    trace = np.asarray(trace)
    shared_trace = trace.ndim == 1
    if not shared_trace and trace.shape[0] != len(grid):
        raise ValueError(f"trace grid dim {trace.shape[0]} != "
                         f"{len(grid)} configurations")
    n_per = trace.shape[-1]

    t0 = time.perf_counter()
    if mode == "vmap" and adaptive:
        # the long-standing vmapped-adaptive-sweeps item: the grid's
        # climbers become tenant LANES of one streams=G compiled program
        # (StepSpec.streams) — per-lane quota and climber registers keep
        # every grid point's history independent, so the results are
        # bit-identical to the sequential per-config runs
        # (tests/test_streams.py pins it).  Lanes advance one shared
        # program, so the grid must agree on the static geometry —
        # capacity/sizing sweeps change it and stay sequential.
        specs = {c.spec() for c in grid}
        if len(specs) != 1:
            raise ValueError(
                "adaptive vmap sweeps run the grid as lanes of ONE "
                "compiled program, which needs one shared static geometry; "
                f"this grid has {len(specs)} distinct geometries "
                "(capacities or sizing differ) — sweep window_fracs or "
                "climb hyperparameters, or use mode='sequential'")
        G = len(grid)
        lspec = specs.pop()
        spec = replace(lspec, streams=G)
        epochs = {int(cl.epoch_len) for cl in climbs}
        if len(epochs) != 1:
            raise ValueError(
                "adaptive vmap sweeps climb in lockstep, so climb.epoch_len "
                f"must be uniform across the grid (got {sorted(epochs)}) — "
                "use mode='sequential' for mixed epoch lengths")
        E = epochs.pop()
        pstack = jnp.stack([c.params(warmup=warmup) for c in grid])
        sstack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[init_step_state(lspec, c.window_cap, c.main_cap)
              for c in grid])
        cstack = jnp.stack([jnp.asarray(cl.resolve(c))
                            for cl, c in zip(climbs, grid)])
        carry = jnp.stack([_climb_carry0(cv) for cv in cstack], axis=1)
        if shared_trace:
            l1, h1 = _trace_lanes(trace)
            lo = jnp.broadcast_to(l1, (G, n_per))
            hi = jnp.broadcast_to(h1, (G, n_per))
        else:
            lanes = [_trace_lanes(t) for t in trace]
            lo = jnp.stack([l for l, _ in lanes])
            hi = jnp.stack([h for _, h in lanes])
        ne = n_per // E
        nfull = ne * E
        st = sstack
        if ne:
            st, _, _, _, carry = _adaptive_runner(spec, "jit", False)(
                pstack, st, _chunk_lanes(lo[:, :nfull], ne, E),
                _chunk_lanes(hi[:, :nfull], ne, E),
                jnp.full((ne,), E, jnp.int32), cstack, carry)
        if n_per - nfull:       # the (< epoch) tail steps but never climbs
            st, _ = _jit_step(spec, pstack, st, lo[:, nfull:], hi[:, nfull:])
        regs = np.asarray(st["regs"])
    elif mode == "vmap":
        # one program for the whole grid: shared (largest) static geometry,
        # per-config capacities traced, excess slots marked as padding
        big = max(grid, key=lambda c: c.capacity)
        # set mode: the whole grid shares the largest config's block shape
        # (ways).  A member whose main_cap falls below the shared MAIN set
        # count would leave most of its sets zero-way — keys could never
        # enter its main table and its hit ratio would silently collapse —
        # so such grids are rejected toward sequential mode.  (Zero-way
        # WINDOW sets are fine: those accesses bypass to main admission.)
        mslots = max(c._table_slots(c.main_cap, big.ways) for c in grid)
        if big.assoc is not None:
            msets = mslots // big.ways
            for c in grid:
                if c.main_cap < msets:
                    raise ValueError(
                        f"vmap assoc sweep: main_cap {c.main_cap} < shared "
                        f"{msets} sets (capacity {c.capacity} vs "
                        f"{big.capacity}); run mode='sequential'")
        spec = big.spec(
            window_slots=max(c._table_slots(c.window_cap, big.ways)
                             for c in grid),
            main_slots=mslots, ways=big.ways)
        pstack = jnp.stack([c.params(warmup=warmup) for c in grid])
        sstack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[init_step_state(spec, c.window_cap, c.main_cap) for c in grid])
        if shared_trace:
            lo, hi = _trace_lanes(trace)
            in_axes = (0, 0, None, None)
        else:
            lanes = [_trace_lanes(t) for t in trace]
            lo = jnp.stack([l for l, _ in lanes])
            hi = jnp.stack([h for _, h in lanes])
            in_axes = (0, 0, 0, 0)
        key = (spec, in_axes)
        if key not in _vmap_cache:
            if len(_vmap_cache) >= _STEP_CACHE_LIMIT:
                _vmap_cache.clear()
            _vmap_cache[key] = jax.jit(jax.vmap(
                lambda p, s, l, h: step_ref(spec, p, s, l, h),
                in_axes=in_axes))
        out_states, _ = _vmap_cache[key](pstack, sstack, lo, hi)
        regs = np.asarray(out_states["regs"])
    elif mode == "sequential":
        # per-config tight specs: sketches sized exactly like the host's
        # per-capacity sizing, one compile per distinct geometry
        if shared_trace:
            lanes = [_trace_lanes(trace)] * len(grid)
        else:
            lanes = [_trace_lanes(t) for t in trace]
        outs = []
        for gi, (c, (l, h)) in enumerate(zip(grid, lanes)):
            spec = c.spec()
            st = init_step_state(spec, c.window_cap, c.main_cap)
            if adaptive:
                st, _, _, _ = _run_adaptive(c, spec, c.params(warmup=warmup),
                                            st, l, h, climbs[gi], "jit",
                                            False, mesh=c.mesh)
                outs.append(st["regs"])
            elif c.shards > 1:
                st, _ = _run_sharded(spec, c.params(warmup=warmup), st,
                                     l, h, c.merge_epoch, "jit", False,
                                     mesh=c.mesh)
                outs.append(st["regs"])
            else:
                outs.append(_jit_step(spec, c.params(warmup=warmup), st,
                                      l, h)[0]["regs"])
        regs = np.stack([np.asarray(r) for r in outs])
    else:
        raise ValueError(f"unknown mode {mode!r}")
    wall = time.perf_counter() - t0

    counted = n_per - warmup
    out = []
    for g, (C, wf) in enumerate(gridlab):
        hits = int(regs[g, R_HITS])
        # _row_extra keeps sweep rows schema-identical to simulate_trace
        # rows (sweep rows used to omit streams/integrity/merge_every)
        extra = {"backend": f"jit+{mode}", "window_frac": wf,
                 "grid": len(grid), "grid_wall_s": wall,
                 "assoc": grid[g].assoc,
                 "device": jax.default_backend(),
                 **_row_extra(grid[g], climbs[g] if adaptive else None,
                              adaptive)}
        if adaptive:
            extra["adaptive"] = True
            extra["final_quota"] = int(regs[g, R_WQUOTA])
        out.append(SimResult(
            policy=_policy_label(grid[g], adaptive),
            cache_size=C, trace=trace_name,
            accesses=counted, hits=hits, hit_ratio=hits / max(1, counted),
            # per-row amortized wall so accesses/wall_s is per-config and
            # comparable to host rows; the grid's total is in grid_wall_s
            wall_s=wall / len(grid), extra=extra))
        if verbose:
            print(f"  {trace_name:>12s} C={C:<7d} wf={wf:<5.2f} "
                  f"hit={out[-1].hit_ratio:.4f}  (grid of {len(grid)}, "
                  f"{wall:.1f}s total)", flush=True)
    return out
