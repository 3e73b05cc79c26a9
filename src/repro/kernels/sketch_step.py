"""Fused device-resident W-TinyLFU simulation step (paper §4, Fig 5).

One launch advances an entire *chunk* of the access trace through the full
W-TinyLFU decision pipeline while every byte of policy state stays
VMEM-resident:

    per access:  doorkeeper insert  +  conservative-update add  (+ §3.3 reset)
                 -> window-LRU / SLRU-main lookup
                 -> on window overflow: candidate & victim frequency estimate
                 -> admission verdict + table update

This replaces the three separate HBM round-trips per decision (sketch_update
-> sketch_estimate -> admission) that made trace simulation launch-bound.

Two table layouts share the step, selected by ``StepSpec.assoc``:

**Flat (assoc=None, the exact path)** — cache tables are fixed-capacity
packed int32 arrays.  Each slot's (valid, segment, LRU-stamp) state is packed
into ONE int32 ``meta``:

      -1              empty slot
      t               probation entry, last-stamped at access t
      2^30 | t        protected entry, last-stamped at access t
      2^31-1          sweep padding (permanently unusable slot)

so a single ``argmin(meta)`` is simultaneously the free-slot finder and
the exact SLRU victim priority (empty < probation LRU < protected LRU),
and a single ``argmin`` over the window's meta is free-slot-else-LRU.
Exact global LRU — but every lookup/victim search is O(capacity), so
per-access cost grows linearly with cache size.

**Set-associative (assoc=W, the O(ways) path)** — each table is
``n_sets × assoc`` rows of one packed int32 record
``[lo, hi, meta, (mset1, mset2,) idx[rows], dkb[dkp]]``; a key hashes to a set
(``sketch_common.set_index``) and every lookup, free-slot search, SLRU
victim priority, and protected-overflow demotion is a contiguous
``dynamic_slice`` gather + reduce over ``assoc`` records — O(ways) per
access, independent of capacity.  LRU and the SLRU segmentation become
*per-set* (hardware-cache / Caffeine-style): the protected budget of a set
is ``max(1, usable_ways * prot_cap // main_cap)``.  Semantics shift from
exact global LRU to per-set LRU, so the contract vs the host exact policy
is hit-ratio tolerance (±0.01 on the golden traces) instead of bitwise
parity; ``step_ref``/``step_pallas`` remain bit-for-bit identical to each
other, and a single-set geometry (n_sets == 1) reproduces the flat path's
hit sequence exactly.

* LRU order is the monotone access index ``t``; each access stamps at most
  one entry per segment (per set), so stamps are unique and ``argmin``
  reproduces the host OrderedDict order exactly.
* hashing is hoisted out of the sequential loop entirely: probe rows,
  doorkeeper bit positions, and both set indices are precomputed vectorized
  over the whole chunk (they do not depend on state) and *stored in the
  tables* next to the key lanes, so estimates of resident candidates/victims
  need no re-hashing, and a displaced window entry carries its own main-table
  set index with it.

Sketch counters are ``counter_bits`` ∈ {4, 8} wide (8 or 4 per int32 word):
4-bit is the paper's §3.4.1 layout (cap ≤ 15, sample_factor ≤ 16); 8-bit
doubles the sketch footprint but lifts the cap to 255 so large
``sample_factor`` configurations no longer need the host engine.

**Sharded sketches (``StepSpec.shards = S``)** — for capacities whose
counters outgrow one core's VMEM, the sketch address space partitions into S
shards: a key's probes are confined to its owning shard's ``width/S``-counter
(and ``dk_bits/S``-doorkeeper-bit) slice, ``counters``/``doorkeeper`` carry
[merged global || shard delta] halves in one buffer, per-access writes land
in the owning shard's slice of the delta half, reads compose global + delta,
and the §3.3 reset moves from the per-access path to the epoch-boundary
``kernels.sketch_merge.merge_halve`` fold (saturating CM-sketch merge +
deferred halving, inside the same compiled program).
``shards=1`` (the default) compiles the identical program — all shard logic
sits under static Python branches, same pattern as ``assoc=None`` /
``adaptive=False``.

Semantics contract (tests/test_sketch_step.py, tests/test_device_simulate.py):

* ``step_ref`` (pure-jnp `lax.scan`) and ``step_pallas`` (fused kernel) are
  bit-for-bit identical, including reset boundaries that straddle chunks —
  in both layouts.
* The sketch substate evolves exactly like ``ref.add_ref`` (no reset) and the
  host ``FrequencySketch`` up to the 32-bit-lane hash family.
* With a collision-free sketch, the per-access hit sequence is bit-for-bit
  the host ``WTinyLFU``'s (flat), resp. the host set-associative twin's
  (``core.policies.SetAssociativeSLRU`` via ``WTinyLFU(assoc=...)``).

Static geometry lives in ``StepSpec``; per-config scalars that may vary
across a vmapped sweep (protected capacity, sample size W, counter cap,
warmup) are a traced int32 ``params`` vector, so one compiled program sweeps
a Cartesian grid of configurations (core/device_simulate.py).  Window/main
capacities below the static slot counts are expressed at init time by marking
the excess slots as padding (init_step_state); in set mode the padding is
distributed over the sets by ``core.hashing.set_ways``; a grid member far
below the shared geometry may leave some sets empty, and keys hashing there
bypass that table (inserts are gated on non-padding slots).

Keys: 64-bit keys arrive as (lo, hi) int32 bit-pattern lanes.  The single
key value 2^64-1 (lanes == -1) is reserved as the padding-slot sentinel and
must not appear in traces.

Aliasing: ``step_pallas`` donates every state buffer (input_output_aliases),
so between chunks the state never round-trips through fresh HBM allocations.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import (WSET_SALT, MSET_SALT, MSET2_SALT, set_ways,
                                shard_geometry)
from .sketch_common import (POLICIES, probe_index, dk_probe_index, set_index,
                            shard_index, halve_words)

# python ints (not jnp scalars): jnp scalars at module scope would be closed
# over as captured constants, which pallas kernels reject
_I32_MAX = 2**31 - 1          # padding-slot meta: never free, never a victim
_PROT = 1 << 30               # meta bit 30: protected segment
_EMPTY = -1                   # meta of an empty (usable) slot

# params vector layout (traced per-config scalars; see make_step_params)
P_WINDOW_CAP = 0              # informational (capacities are baked at init)
P_MAIN_CAP = 1
P_PROT_CAP = 2
P_SAMPLE = 3                  # W; 0 disables the automatic reset
P_CAP = 4                     # counter saturation (< 2**counter_bits)
P_WARMUP = 5                  # accesses before hits start counting
NPARAMS = 8

# regs vector layout (mutable int32 scalar state)
R_SIZE = 0                    # sketch additions since last reset
R_PCOUNT = 1                  # protected entries within main (flat path only)
R_T = 2                       # global access index == LRU stamp
R_HITS = 3                    # counted hits (post warmup)
# adaptive-mode registers (zero / inert when StepSpec.adaptive is False)
R_WQUOTA = 4                  # runtime window capacity (hill-climbed)
R_WCOUNT = 5                  # resident window entries (flat adaptive only)
R_MCOUNT = 6                  # resident main entries (flat adaptive only)
R_EHITS = 7                   # hits this epoch (reset by rebalance)
NREGS = 8
# admission event counters, present only with StepSpec.events (regs grows
# to NREGS_EVENTS slots; without the flag the program is unchanged)
R_CANDS = 8                   # window overflows that pushed a candidate
R_ADMIT = 9                   # candidates that beat the victim, replaced it
R_REJECT = 10                 # candidates that lost to the victim
R_RESETS = 11                 # accesses on which the §3.3 reset fired
NREGS_EVENTS = 12

# packed set-associative record columns (window carries two extra lanes: the
# resident key's two candidate main-table set indices, so a displaced
# candidate needs no re-hash to find its victim sets)
WT_LO, WT_HI, WT_META, WT_MSET, WT_MSET2 = 0, 1, 2, 3, 4
MT_LO, MT_HI, MT_META = 0, 1, 2

# mesh axis name of the multi-device sharded-sketch run (StepSpec.mesh_devices
# > 0 — the step then executes inside a shard_map over
# distributed.mesh.make_shard_mesh and the delta halves are device-local)
MESH_AXIS = "shard"


def _pow2(x: int) -> bool:
    return x > 0 and (x & (x - 1)) == 0


@dataclass(frozen=True)
class StepSpec:
    """Static geometry of one simulated W-TinyLFU instance.

    Every field is compile-time static: two ``StepSpec`` values that differ
    in any field compile (and cache) separate programs.  Per-config scalars
    that may vary across a vmapped sweep live in the traced ``params``
    vector instead (:func:`make_step_params`).

    Field reference (see docs/API.md for the rendered version):

    ``width``
        Sketch counters per row.  Power of two, multiple of 8 (counters are
        packed 8- or 4-per-int32 word).  With ``shards=S`` also a multiple
        of ``8*S`` — each shard owns a contiguous ``width/S`` slice.
    ``rows`` (default 4)
        CM-sketch depth: independent probe rows, estimate = min over rows.
        At most ``len(PROBE_SALTS)`` (8).
    ``dk_bits`` (default 0)
        Doorkeeper Bloom-filter bits (paper §3.4.2).  0 disables the
        doorkeeper; otherwise a power of two >= 32 (packed 32-per-int32;
        with ``shards=S``: a multiple of ``32*S``).
    ``dk_probes`` (default 3)
        Bloom probes per doorkeeper insert/query.
    ``window_slots`` / ``main_slots`` (default 1)
        Static table sizes; must be >= any window/main capacity the params
        configure (excess slots become init-time padding, or runtime
        headroom when ``adaptive``).  In set mode each must be
        ``assoc * pow2`` (sets x ways).
    ``assoc`` (default None)
        None = flat exact tables (global LRU/SLRU, O(capacity) per access).
        W = W-way set-associative layout, O(ways) per access.  Interaction:
        vmapped sweeps share one static geometry — every grid member must
        keep ``main_cap >= shared main set count`` (enforced by
        ``simulate_sweep``) or its main table would be unreachable.
    ``counter_bits`` (default 4)
        Packed sketch counter width: 4 (cap <= 15, the paper's §3.4.1
        layout) or 8 (cap <= 255, doubles the sketch footprint, lifts the
        ``sample_factor > 16`` host-engine limitation).
    ``adaptive`` (default False)
        Runtime window quota in ``regs[R_WQUOTA]`` hill-climbed at epoch
        boundaries (``core.device_simulate.ClimbSpec``).  False compiles
        the identical program as before the feature existed.  Interaction:
        adaptive sweeps are sequential-mode only (quota histories diverge,
        defeating vmap's shared geometry).
    ``shards`` (default 1)
        Frequency-sketch shards (pow2).  ``S > 1`` partitions the sketch
        address space: a key's probes are confined to its owning shard's
        ``width/S``-counter (and ``dk_bits/S``-bit) slice, the sketch
        buffers carry [merged global || shard delta] halves, per-access
        writes land in the owning shard's slice of the delta half, reads
        compose global + delta, and the §3.3 reset moves from the
        per-access path to the epoch-boundary
        :func:`repro.kernels.sketch_merge.merge_halve` fold.  ``shards=1``
        compiles the identical program (all shard logic is under static
        Python branches).  Interaction: sharded runs are epoch-chunked
        (``merge_every``) and sequential-sweep only, like ``adaptive``.
    ``mesh_devices`` (default 0)
        Multi-device sharded execution (``core.device_simulate``
        ``DeviceWTinyLFU(mesh=)``): the step runs inside a ``shard_map``
        over a 1-D ``("shard",)`` mesh of that many devices
        (``distributed.mesh.make_shard_mesh``), the sketch delta halves
        live as shard-major arrays partitioned along the mesh axis
        (``dcounters``/``ddoorkeeper`` state keys — per-access writes are
        device-local), the global halves stay replicated, and the
        per-access path exchanges NOTHING: all cross-device traffic is
        per-epoch-chunk (``mesh_exchange``).  Requires
        ``shards % mesh_devices == 0`` (block placement: device ``d``
        owns shards ``[d*S/D, (d+1)*S/D)``, matching
        ``distributed.mesh.shard_placement``).  0 = single-device layout.
    ``mesh_exchange`` (default "chunk")
        Cross-device exchange cadence of the mesh run (inert at
        ``mesh_devices=0``).  ``"chunk"`` — exact chunked exchange: the
        runner all-gathers the shard deltas ONCE per run, every device
        replays each merge epoch as the literal (replicated) single-device
        sharded program, and re-splits its local delta block at the end;
        bit-identical to the single-device sharded run.  ``"stale"`` —
        speculative stale-global admission: per-access estimates read only
        the replicated global halves (:func:`_estimate_pair_stale` — stale
        by at most one merge epoch, zero per-access collectives) and
        reconcile at the once-per-epoch
        :func:`repro.kernels.sketch_merge.merge_halve_mesh` all-gather;
        hit ratios land in the goldens-±0.01 tier (host twin:
        ``core.sketch.ShardedFrequencySketch(stale_estimates=True)``).
    ``streams`` (default 1)
        Lane-batched multi-tenant execution: ``B > 1`` advances B
        INDEPENDENT cache instances in lockstep inside one compiled scan.
        Every mutable state leaf gains a leading lane axis ``(B, …)``, key
        lanes arrive as ``(B, T)``, and the step dispatches through
        ``jax.vmap`` of the ``streams=1`` program — with the per-access
        single-slot writes re-expressed as fused masked selects
        (:data:`_LANE_TRACE`), because vmapping a per-lane-indexed
        ``dynamic_update_slice`` would lower to one XLA-CPU scatter per
        write site (~7µs FIXED cost each, regardless of operand size —
        measured to cap lane scaling at ~2x).  ``streams=1`` never takes
        the dispatch and compiles the byte-identical unbatched program.
        Interaction: incompatible with ``mesh_devices`` (the lanes would
        vmap over the mesh axis the shard_map already owns); the pallas
        backend batches through pallas' own vmap rule.
    ``policy`` (default "wtinylfu")
        Admission/victim rules applied on top of the policy-agnostic
        set-associative machinery (:data:`repro.kernels.sketch_common.
        POLICIES`).  ``"wtinylfu"`` is the full engine and the only value
        the flat/adaptive/sharded/mesh/integrity modes accept; the
        competitor policies (``"s3fifo"``, ``"arc"``, ``"lfu"``) require
        ``assoc`` and run inside the same fused scan — same packed
        records, per-set gather+reduce, write discipline, and ``streams``
        lane batching.  ``"arc"`` additionally requires ``dk_bits > 0``
        (its B1/B2 ghost lists are Bloom filters addressed by the
        doorkeeper probe schedule, stored in a dedicated ``"ghost"``
        state buffer).  ``policy="wtinylfu"`` compiles the byte-identical
        program to a spec without the field (tests/test_policy_panel.py
        pins the lowered HLO).
    ``integrity`` (default False)
        Self-healing sketch integrity (requires ``shards > 1``).  Adds a
        ``"csum"`` state vector of ``shards + 1`` int32 words: per-shard
        :func:`repro.kernels.sketch_common.checksum_words` checksums over
        the global sketch halves (which are read-only between merge
        boundaries — per-access writes land only in the delta halves),
        plus a cumulative quarantined-shard counter in the last word.  The
        epoch-boundary :func:`repro.kernels.sketch_merge.merge_halve` fold
        verifies each shard's checksum before merging; a mismatched shard
        is QUARANTINED — its global and delta slices are zeroed — and the
        paper's §3.3 aging re-learns its counts within a few sample
        periods.  False compiles the identical program.
    ``events`` (default False)
        Admission event counters (set-associative W-TinyLFU path only):
        ``regs`` grows from ``NREGS`` to ``NREGS_EVENTS`` slots, and step 6
        of :func:`_one_access_set` adds per access into ``R_CANDS`` (a
        window overflow pushed a candidate), ``R_ADMIT`` (the candidate
        beat a resident victim's estimate and replaced it), ``R_REJECT``
        (it lost) and ``R_RESETS`` (the §3.3 reset fired).  An insert into
        a free slot counts only as a candidate.  False compiles the
        identical program.
    """
    width: int                    # sketch counters per row (pow2, mult of 8)
    rows: int = 4
    dk_bits: int = 0              # doorkeeper bits (pow2 >= 32); 0 = off
    dk_probes: int = 3
    window_slots: int = 1         # window table size (>= any window_cap used)
    main_slots: int = 1           # main table size (>= any main_cap used)
    assoc: int | None = None      # ways per set; None = flat exact tables
    counter_bits: int = 4         # sketch counter width: 4 (cap 15) or 8 (255)
    adaptive: bool = False        # runtime window quota (regs[R_WQUOTA])
    shards: int = 1               # sketch shards (pow2); >1 = delta/global
    mesh_devices: int = 0         # shard_map devices; 0 = single-device
    mesh_exchange: str = "chunk"  # mesh cadence: "chunk" exact | "stale"
    integrity: bool = False       # per-shard checksums + quarantine fold
    streams: int = 1              # lane-batched tenant instances (B >= 1)
    policy: str = "wtinylfu"      # admission/victim rules (POLICIES enum)
    events: bool = False          # admission event counters in regs

    def __post_init__(self):
        assert self.policy in POLICIES, (
            f"policy {self.policy!r} must be one of {POLICIES}")
        if self.policy != "wtinylfu":
            assert self.assoc is not None, (
                f"policy {self.policy!r} runs on the set-associative "
                "machinery only (assoc=W); the flat exact tables are "
                "W-TinyLFU-specific")
            assert self.shards == 1 and self.mesh_devices == 0, (
                f"policy {self.policy!r} does not support sketch sharding "
                "or mesh execution (competitor policies exist for "
                "apples-to-apples sweeps, not production scale-out)")
            assert not self.adaptive and not self.integrity, (
                f"policy {self.policy!r} cannot combine with adaptive/"
                "integrity (both are W-TinyLFU-engine features)")
        if self.policy == "arc":
            assert self.dk_bits > 0, (
                "policy='arc' needs dk_bits > 0: its B1/B2 ghost lists "
                "are Bloom filters addressed by the doorkeeper probe "
                "schedule")
        assert self.streams >= 1, "streams must be >= 1"
        if self.streams > 1:
            assert self.mesh_devices == 0, (
                "streams (lane-batched tenants) cannot combine with "
                "mesh_devices (the lanes would vmap over the mesh axis "
                "the shard_map already owns)")
        if self.events:
            assert (self.assoc is not None and self.policy == "wtinylfu"
                    and self.shards == 1 and not self.adaptive), (
                "events counts the set-associative W-TinyLFU path's "
                "admissions (assoc=W, policy 'wtinylfu', shards=1, "
                "adaptive=False)")
        if self.integrity:
            assert self.shards > 1, (
                "integrity checksums cover the per-shard global sketch "
                "halves, which only exist at shards > 1")
        assert self.mesh_exchange in ("chunk", "stale"), (
            f"mesh_exchange {self.mesh_exchange!r} must be 'chunk' (exact "
            "chunked exchange) or 'stale' (speculative stale-global "
            "admission)")
        if self.mesh_devices:
            assert self.shards > 1, "mesh execution requires shards > 1"
            assert self.shards % self.mesh_devices == 0, (
                f"shards {self.shards} must be a multiple of mesh_devices "
                f"{self.mesh_devices} (block placement)")
        assert _pow2(self.width) and self.width % 8 == 0
        assert self.counter_bits in (4, 8)
        assert self.dk_bits == 0 or (_pow2(self.dk_bits) and self.dk_bits >= 32)
        assert self.window_slots >= 1 and self.main_slots >= 1
        # validates shards pow2 + per-shard word alignment
        shard_geometry(self.width, self.dk_bits, self.shards)
        if self.assoc is not None:
            assert self.assoc >= 1
            assert self.window_slots % self.assoc == 0 and \
                _pow2(self.window_slots // self.assoc), \
                "window_slots must be assoc * pow2-sets"
            assert self.main_slots % self.assoc == 0 and \
                _pow2(self.main_slots // self.assoc), \
                "main_slots must be assoc * pow2-sets"

    @property
    def counters_per_word(self) -> int:
        return 32 // self.counter_bits

    @property
    def words_per_row(self) -> int:
        return self.width // self.counters_per_word

    @property
    def counter_cap_max(self) -> int:
        return (1 << self.counter_bits) - 1

    @property
    def dk_words(self) -> int:
        return max(1, self.dk_bits // 32)

    @property
    def width_shard(self) -> int:     # counters per row owned by one shard
        return self.width // self.shards

    @property
    def dk_bits_shard(self) -> int:   # doorkeeper bits owned by one shard
        return self.dk_bits // self.shards

    @property
    def counter_words(self) -> int:   # words in the global counter image
        return self.rows * self.words_per_row

    @property
    def sketch_halves(self) -> int:   # sharded: [global || delta] halves
        return 2 if self.shards > 1 else 1

    @property
    def local_shards(self) -> int:    # shards owned by one mesh device
        return self.shards // max(1, self.mesh_devices)

    @property
    def wps_shard(self) -> int:       # counter words per row per shard
        return self.words_per_row // self.shards

    @property
    def dkw_shard(self) -> int:       # doorkeeper words per shard
        return max(1, self.dk_words // self.shards)

    @property
    def dkp(self) -> int:         # stored doorkeeper probes per table entry
        return self.dk_probes if self.dk_bits else 1

    @property
    def window_sets(self) -> int:
        return self.window_slots // self.assoc

    @property
    def main_sets(self) -> int:
        return self.main_slots // self.assoc

    @property
    def wcols(self) -> int:       # packed window record width (set mode)
        return 5 + self.rows + self.dkp

    @property
    def mcols(self) -> int:       # packed main record width (set mode)
        return 3 + self.rows + self.dkp


def make_step_params(window_cap: int, main_cap: int, prot_cap: int,
                     sample_size: int, cap: int, warmup: int = 0,
                     counter_bits: int = 4) -> jnp.ndarray:
    """Pack per-config scalars into the traced (NPARAMS,) int32 vector.

    ``counter_bits`` must match the ``StepSpec`` these params will run
    against: a cap above the counter mask would make the minimal-increment
    bump fire on saturated counters and carry into the NEIGHBORING packed
    counter, silently corrupting another key's estimate.
    """
    assert 1 <= cap <= (1 << counter_bits) - 1, (
        f"cap {cap} does not fit {counter_bits}-bit counters")
    p = [int(window_cap), int(main_cap), int(prot_cap), int(sample_size),
         int(cap), int(warmup)] + [0] * (NPARAMS - 6)
    return jnp.asarray(p, jnp.int32)


def _state_keys(spec: StepSpec) -> tuple[str, ...]:
    # sharded mode keeps the same keys: "counters"/"doorkeeper" simply carry
    # TWO halves — [merged global || shard-partitioned delta].  One buffer
    # (not separate delta arrays) so the per-access DUS write chain has the
    # exact shape XLA CPU already updates in place on the unsharded path;
    # separate delta buffers measured 4 full copies per access at big widths.
    # Mesh mode is the exception: "counters"/"doorkeeper" hold ONLY the
    # replicated global halves and the deltas live in shard-major
    # "dcounters"/"ddoorkeeper" arrays partitioned along the mesh axis.
    mesh = ("dcounters", "ddoorkeeper") if spec.mesh_devices else ()
    load = (("wsl", "wuw") if spec.adaptive and spec.assoc is not None
            else ())
    csum = ("csum",) if spec.integrity else ()
    # ARC's B1/B2 ghost Blooms: one buffer of 2*dk_words int32 words
    # (B1 = [0, dk_words), B2 = [dk_words, 2*dk_words))
    ghost = ("ghost",) if spec.policy == "arc" else ()
    if spec.assoc is None:
        return ("counters", "doorkeeper", *mesh, "wlo", "whi", "wmeta",
                "widx", "wdkb", "mlo", "mhi", "mmeta", "midx", "mdkb",
                *csum, "regs")
    return ("counters", "doorkeeper", *mesh, "wtab", "mtab", *ghost, *load,
            *csum, "regs")


def init_step_state(spec: StepSpec, window_cap: int | None = None,
                    main_cap: int | None = None) -> dict:
    """Zeroed simulation state (a pytree of int32 device arrays).

    ``window_cap``/``main_cap`` below the static slot counts mark the excess
    slots as permanent padding — this is how one static ``StepSpec`` hosts a
    vmapped sweep over different cache sizes.  In set mode the padding is
    distributed over the sets (``core.hashing.set_ways``): the first
    ``cap % n_sets`` sets keep one extra usable way; capacities below the
    set count leave the excess sets empty (keys hashing there bypass that
    table — a documented vmapped-sweep approximation).

    ``spec.adaptive`` flips the capacity mechanism from init-time padding to
    runtime state: every slot is usable at the static level, ``window_cap``
    seeds the ``regs[R_WQUOTA]`` register (the hill-climbed runtime window
    quota), and the per-access step derives both tables' effective
    capacities from the registers instead of from padding (flat: resident
    counts gate inserts; set: per-set usable-way masks).
    """
    if spec.streams > 1:
        # every lane starts from the identical zeroed instance; per-lane
        # capacities (vmapped sweeps) stack per-config states instead
        base = init_step_state(replace(spec, streams=1), window_cap,
                               main_cap)
        return jax.tree_util.tree_map(
            lambda v: jnp.repeat(v[None], spec.streams, axis=0), base)
    wcap = spec.window_slots if window_cap is None else int(window_cap)
    mcap = spec.main_slots if main_cap is None else int(main_cap)
    assert 1 <= wcap <= spec.window_slots and 1 <= mcap <= spec.main_slots

    regs = jnp.zeros((NREGS_EVENTS if spec.events else NREGS,), jnp.int32)
    if spec.adaptive:
        regs = regs.at[R_WQUOTA].set(wcap)
    # sharded (sketch_halves == 2): the arrays carry [global || delta]
    # halves in ONE buffer — shard s owns words [s*words/S, (s+1)*words/S)
    # of every row slice in the delta half, and per-access writes land only
    # there (probe indices are shard-confined).  Mesh mode splits the delta
    # out into shard-major arrays (axis 0 = shard) so a NamedSharding /
    # shard_map along ("shard",) makes per-access delta writes device-local.
    if spec.mesh_devices:
        common = {
            "counters": jnp.zeros((spec.counter_words,), jnp.int32),
            "doorkeeper": jnp.zeros((spec.dk_words,), jnp.int32),
            "dcounters": jnp.zeros(
                (spec.shards, spec.rows, spec.wps_shard), jnp.int32),
            "ddoorkeeper": jnp.zeros((spec.shards, spec.dkw_shard),
                                     jnp.int32),
            "regs": regs,
        }
    else:
        common = {
            "counters": jnp.zeros((spec.sketch_halves * spec.counter_words,),
                                  jnp.int32),
            "doorkeeper": jnp.zeros((spec.sketch_halves * spec.dk_words,),
                                    jnp.int32),
            "regs": regs,
        }
    if spec.integrity:
        # [0:S] per-shard checksums of the global sketch halves, [S] the
        # cumulative quarantined-shard count.  Zeros are the correct seed:
        # checksum_words of all-zero buffers is 0.
        common["csum"] = jnp.zeros((spec.shards + 1,), jnp.int32)
    if spec.policy == "arc":
        # B1/B2 ghost Blooms (dk_bits each), empty at init
        common["ghost"] = jnp.zeros((2 * spec.dk_words,), jnp.int32)
    if spec.adaptive and spec.assoc is not None:
        # load-aware window quota distribution state (ISSUE 5): per-set
        # window access counts this epoch + the current usable-way vector
        # (seeded with the uniform set_ways rule, which the per-access path
        # used to compute arithmetically)
        nws = spec.window_slots // spec.assoc
        common["wsl"] = jnp.zeros((nws,), jnp.int32)
        common["wuw"] = jnp.asarray(set_ways(wcap, nws), jnp.int32)
    if spec.adaptive:
        # no init-time padding: capacities live in regs/params at runtime
        wcap = spec.window_slots
        mcap = spec.main_slots

    if spec.assoc is None:
        def table(slots, cap):
            pad = jnp.arange(slots) >= cap
            return {
                # all non-resident slots hold the sentinel key (lanes -1) so
                # no real key — including key 0 — can match an unoccupied slot
                "lo": jnp.full((slots,), -1, jnp.int32),
                "hi": jnp.full((slots,), -1, jnp.int32),
                "meta": jnp.where(pad, _I32_MAX, _EMPTY).astype(jnp.int32),
                "idx": jnp.zeros((slots, spec.rows), jnp.int32),
                "dkb": jnp.zeros((slots, spec.dkp), jnp.int32),
            }

        w, m = table(spec.window_slots, wcap), table(spec.main_slots, mcap)
        return {**common,
                "wlo": w["lo"], "whi": w["hi"], "wmeta": w["meta"],
                "widx": w["idx"], "wdkb": w["dkb"],
                "mlo": m["lo"], "mhi": m["hi"], "mmeta": m["meta"],
                "midx": m["idx"], "mdkb": m["dkb"]}

    def set_table(slots, cap, ncols, meta_col):
        n_sets = slots // spec.assoc
        ways = np.asarray(set_ways(cap, n_sets))
        way_of = np.arange(slots) % spec.assoc
        pad = way_of >= ways[np.arange(slots) // spec.assoc]
        tab = np.zeros((slots, ncols), np.int32)
        tab[:, 0] = -1
        tab[:, 1] = -1
        tab[:, meta_col] = np.where(pad, _I32_MAX, _EMPTY)
        return jnp.asarray(tab)

    return {**common,
            "wtab": set_table(spec.window_slots, wcap, spec.wcols, WT_META),
            "mtab": set_table(spec.main_slots, mcap, spec.mcols, MT_META)}


# ---------------------------------------------------------------------------
# probe precomputation — vectorized over the chunk, outside the scan
# ---------------------------------------------------------------------------

def precompute_probes(spec: StepSpec, lo: jnp.ndarray, hi: jnp.ndarray):
    """(B,) key lanes -> ((B, rows) probes, (B, dkp) doorkeeper bits,
    (B,) window set, (B, 2) main set choices).

    Pure functions of the key, hoisted out of the sequential loop and stored
    alongside resident entries so the loop body never hashes.  Set indices
    are zeros in flat mode.  Each key gets TWO candidate main sets
    (power-of-two-choices placement): the resident copy lives in exactly one,
    lookups probe both, and the insert victim is the weakest of both sets'
    2*ways records.

    ``spec.shards > 1`` confines every probe to the key's owning shard:
    probe = shard * width_shard + (hash & (width_shard - 1)), and likewise
    for doorkeeper bits — so the per-access sketch update touches only the
    owning shard's slice of the delta arrays.  At shards=1 the expressions
    reduce to the unsharded ones bit-for-bit.
    """
    if spec.shards > 1:
        ks = shard_index(lo, hi, spec.shards)
        idx = jnp.stack([ks * spec.width_shard
                         + probe_index(lo, hi, r, spec.width_shard)
                         for r in range(spec.rows)], axis=-1)
        if spec.dk_bits:
            dkb = jnp.stack([ks * spec.dk_bits_shard
                             + dk_probe_index(lo, hi, p, spec.dk_bits_shard)
                             for p in range(spec.dk_probes)], axis=-1)
        else:
            dkb = jnp.zeros(lo.shape + (1,), jnp.int32)
    else:
        idx = jnp.stack([probe_index(lo, hi, r, spec.width)
                         for r in range(spec.rows)], axis=-1)
        if spec.dk_bits:
            dkb = jnp.stack([dk_probe_index(lo, hi, p, spec.dk_bits)
                             for p in range(spec.dk_probes)], axis=-1)
        else:
            dkb = jnp.zeros(lo.shape + (1,), jnp.int32)
    if spec.assoc is not None:
        wset = set_index(lo, hi, spec.window_sets, WSET_SALT)
        mset = jnp.stack([set_index(lo, hi, spec.main_sets, MSET_SALT),
                          set_index(lo, hi, spec.main_sets, MSET2_SALT)],
                         axis=-1)
    else:
        wset = jnp.zeros(lo.shape, jnp.int32)
        mset = jnp.zeros(lo.shape + (2,), jnp.int32)
    return idx, dkb, wset, mset


# ---------------------------------------------------------------------------
# packed access records — the one way the set path makes a table record
# ---------------------------------------------------------------------------

# accesses per block of packed records: ``step_ref`` builds a block's
# records when its scan reaches the block, so their buffer stays bounded
# whatever the trace length.  On the TPU a record's lanes pad to 128, 512 B
# an access: a whole-trace buffer would fill 16 GB at ~31M accesses, and a
# block is 2 MB a stream (G times that for a vmapped sweep of G configs).
_RECORD_BLOCK = 1 << 12


def access_records(lo, hi, kidx, kdkb, kmset) -> jnp.ndarray:
    """Window records ``[lo, hi, 0, mset1, mset2, idx[rows], dkb[dkp]]`` of
    accesses, along the last axis, with the meta lane zero.

    ``step_ref`` builds them a block of :data:`_RECORD_BLOCK` accesses at a
    time, outside the access scan, and scans the ``(K, wcols)`` block as
    one more input: each access then reads its record as one row slice,
    where assembling it in the scan body from scalars costs a
    scalar-to-vector move per lane, twice an access on the TPU.  A
    lane-batched run (:data:`_LANE_TRACE`) builds each access's record in
    the body from its ``(B,)`` lane vectors instead.
    """
    z = jnp.zeros_like(lo)
    return jnp.concatenate(
        [jnp.stack([lo, hi, z, kmset[..., 0], kmset[..., 1]], axis=-1),
         kidx, kdkb], axis=-1).astype(jnp.int32)


def _with_meta(rec: jnp.ndarray, meta) -> jnp.ndarray:
    """``rec`` with its meta lane (``WT_META == MT_META``) set: one select."""
    lane = jnp.arange(rec.shape[-1], dtype=jnp.int32)
    return jnp.where(lane == WT_META, meta, rec)


def _main_record(rec: jnp.ndarray) -> jnp.ndarray:
    """The main-table record of a window record: its two set lanes dropped."""
    return jnp.concatenate([rec[:WT_META + 1], rec[WT_MSET2 + 1:]])


# ---------------------------------------------------------------------------
# functional single-access step — the one source of truth for both backends
# ---------------------------------------------------------------------------

def _row_offsets(spec: StepSpec) -> jnp.ndarray:
    return (jnp.arange(spec.rows, dtype=jnp.int32) * spec.words_per_row)


def _ds_gather(arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """(k,) positions -> (k,) values as UNROLLED 1-element dynamic slices.

    The sharded path reads the doubled [global || delta] sketch buffers;
    above ~256KB of operand XLA CPU's parallel task partitioner starts
    multithreading the k-element gather fusions (outer_dimension_partitions
    on a 3..8-element output), putting a thread-pool dispatch on every
    access — measured 3-5x at width 2^17.  Scalar dynamic slices are
    costed by the slice, not the operand, and a 1-element output cannot be
    partitioned.

    Lane mode (:data:`_LANE_TRACE`): one fused fancy-indexing gather — the
    unrolled scalar slices would batch into k separate gather ops, and the
    per-tenant buffers of a lane-batched run sit far below the partitioner
    cliff the unrolling works around.  (A one-hot select-and-sum
    contraction was tried instead and measured ~25% SLOWER at B=64: the
    reduce roots fragment the fused where-chains.)
    """
    if _LANE_TRACE[0]:
        return arr[idx]
    return jnp.concatenate([jax.lax.dynamic_slice(arr, (idx[i],), (1,))
                            for i in range(idx.shape[0])])


# operand bytes beyond which the unsharded sketch reads switch from fused
# fancy-indexing gathers to the unrolled-scalar-slice discipline: the
# partitioner cliff lands at ~512KB single-half buffers (width 2^18 at the
# default geometry — ROADMAP "XLA-CPU cost-model cliffs"), while BELOW it
# the fused gathers are measurably cheaper (~1.4x at C=512; the same
# size-dependent trade as the flat path's fused masked reset).  The sharded
# branches stay unconditionally unrolled — their doubled buffers cliff a
# tier earlier and PR 4 measured them there.
_PARTITION_CLIFF_BYTES = 1 << 19


def _big_operand(nwords: int) -> bool:
    return nwords * 4 >= _PARTITION_CLIFF_BYTES


# ---------------------------------------------------------------------------
# lane-batched write discipline (StepSpec.streams > 1)
# ---------------------------------------------------------------------------
# Trace-time flag: True only while the streams dispatcher (_step_lanes) is
# vmapping the streams=1 program over the lane axis.  Under vmap, every
# single-slot write whose index is traced PER LANE (argmin/argmax results,
# hashed probe words) would batch from dynamic_update_slice into an XLA
# scatter — and on XLA CPU each scatter op carries a ~7µs FIXED dispatch
# cost regardless of operand size, which caps lane scaling at ~2x (measured;
# the scatter "unique_indices" hints make it WORSE).  The helpers below emit
# today's exact .at[]/DUS expressions when the flag is off — so the
# streams=1 trace stays byte-identical — and fused masked selects when it is
# on: chained one-hot `where` passes over the same buffer fuse into ~one
# elementwise pass (cost ∝ bytes, no per-op penalty), which is what makes
# thousands of small tenant caches per step pay off.  Out-of-bounds
# semantics differ (.at clamps, the mask drops) but every wrapped index is
# an argmin/argmax/hash result, provably in bounds.  The flag is consulted
# at TRACE time only; cache safety follows from the jit key: traces with the
# flag on are only ever produced under a spec whose ``streams`` differs.
_LANE_TRACE = [False]


def _barrier(x):
    """``optimization_barrier`` — identity under lanes: the barrier is an
    XLA-CPU scheduling hint for the in-place DUS discipline (which the lane
    form replaces with fused selects) and it has no vmap batching rule."""
    if _LANE_TRACE[0]:
        return x
    return jax.lax.optimization_barrier(x)


def _lset(arr, j, v, pred=None):
    """``arr.at[j].set(where(pred, v, arr[j]))`` — scatter-free under lanes.

    The predicate folds INTO the one-hot mask in lane mode (one fused
    select, NO ``arr[j]`` gather): the gathers the unbatched expression
    embeds would otherwise break the fused where-chain into separate
    full-buffer passes, which measured ~100 unfused (B, N) sweeps per step.
    """
    if not _LANE_TRACE[0]:
        if pred is None:
            return arr.at[j].set(v)
        return arr.at[j].set(jnp.where(pred, v, arr[j]))
    iota = jnp.arange(arr.shape[0], dtype=jnp.int32)
    m = (iota == j) if pred is None else ((iota == j) & pred)
    return jnp.where(m, v, arr)


def _lset_row(arr, j, row, pred=None):
    """``arr.at[j].set(where(pred, row, arr[j]))`` (2-D arr, row write)."""
    if not _LANE_TRACE[0]:
        if pred is None:
            return arr.at[j].set(row)
        return arr.at[j].set(jnp.where(pred, row, arr[j]))
    iota = jnp.arange(arr.shape[0], dtype=jnp.int32)
    m = (iota == j) if pred is None else ((iota == j) & pred)
    return jnp.where(m[:, None], row[None, :], arr)


def _lset_col(arr, col, v):
    """``arr.at[:, col].set(v)`` (STATIC col) — scatter-free variant."""
    if not _LANE_TRACE[0]:
        return arr.at[:, col].set(v)
    iota = jnp.arange(arr.shape[1], dtype=jnp.int32)
    return jnp.where(iota[None, :] == col, v[:, None], arr)


def _ldus1(arr, upd, j):
    """``dynamic_update_slice(arr, upd, (j,))`` with a (1,) update."""
    if not _LANE_TRACE[0]:
        return jax.lax.dynamic_update_slice(arr, upd, (j,))
    iota = jnp.arange(arr.shape[0], dtype=jnp.int32)
    return jnp.where(iota == j, upd[0], arr)


def _ldus_block(tab, blk, s):
    """Whole-set block write into the scan's set-row table layout
    (:func:`_set_rows`): row ``s`` takes the (A, cols) block ``blk``.

    Lane mode selects the target row with a one-hot broadcast — a generic
    batched row update would instead become a scatter.
    """
    row = blk.reshape(1, -1)
    if not _LANE_TRACE[0]:
        return jax.lax.dynamic_update_slice(tab, row, (s, 0))
    row = jnp.pad(row, ((0, 0), (0, tab.shape[1] - row.shape[1])))
    iota = jnp.arange(tab.shape[0], dtype=jnp.int32)
    return jnp.where((iota == s)[:, None], row, tab)


# Inside the access scan every set-associative table is held as SET ROWS:
# set s is row s of an (n_sets, P) array, its A records packed in the first
# A*cols words and P rounded up to the 128-lane tile.  The canonical
# (n_sets*A, cols) record layout tiles badly on the TPU: XLA keeps the
# carried table column-major (records on lanes, so the few columns are not
# padded to 128) and then relayouts the WHOLE table on every access to read
# a set's rows — ~1.5M cycles per access at C=2^20 in the v5e-compiled
# step.  As set rows, a set read is one dynamic row slice and a set write
# one row update in whatever layout either backend picks.  step_ref (and
# the Pallas kernel) convert at entry and exit, so state outside the scan
# keeps the canonical layout.

def _set_rows(tab: jnp.ndarray, A: int) -> jnp.ndarray:
    n, cols = tab.shape
    w = A * cols
    return jnp.pad(tab.reshape(n // A, w), ((0, 0), (0, -w % 128)))


def _set_block(rows: jnp.ndarray, s, A: int, cols: int) -> jnp.ndarray:
    """Set ``s`` of a set-row table as its (A, cols) record block."""
    return jax.lax.dynamic_slice(rows, (s, 0), (1, A * cols)).reshape(A, cols)


def _scan_tables(spec: StepSpec, state: dict, to_rows: bool) -> dict:
    """Canonical record tables <-> the scan's set rows (set mode only)."""
    if spec.assoc is None:
        return state
    A = spec.assoc

    def conv(tab, cols):
        if to_rows:
            return _set_rows(tab, A)
        return tab[:, :A * cols].reshape(-1, cols)
    return {**state, "wtab": conv(state["wtab"], spec.wcols),
            "mtab": conv(state["mtab"], spec.mcols)}


def _counter_vals(spec: StepSpec, words: jnp.ndarray,
                  idx: jnp.ndarray) -> jnp.ndarray:
    """counter_bits-wide counter values at probe positions idx (…, rows)."""
    sub = idx & (spec.counters_per_word - 1)
    return ((words >> (sub * spec.counter_bits))
            & jnp.int32(spec.counter_cap_max))


def _word_of(spec: StepSpec, idx: jnp.ndarray) -> jnp.ndarray:
    return idx >> (3 if spec.counter_bits == 4 else 2)


def _sketch_add(spec: StepSpec, params, counters, dk, size, kidx, kdkb,
                *, use_cond: bool = False):
    """FrequencySketch.add(): doorkeeper gate -> minimal increment -> reset.

    ``kidx`` (rows,) precomputed probe indices; ``kdkb`` (dkp,) doorkeeper
    bit positions.  Row gathers/scatters are one vectorized op each.

    ``use_cond`` runs the §3.3 reset as a ``lax.cond`` so the O(width)
    halving pass executes only on the accesses where it actually fires
    (the set-associative path needs this for capacity-independent access
    cost); the flat path keeps the fused masked ``where`` which measured
    faster at its small sizes.

    Sharded mode (``spec.shards > 1``): ``counters``/``dk`` carry
    [global || delta] halves in one buffer.  Only the delta half is
    written (probe indices confine the writes to the owning shard's
    slice); a counter's effective value is global+delta and a doorkeeper
    bit is global|delta, so between merges the combined structure evolves
    exactly like the unsharded sketch.  The §3.3 reset is SKIPPED here —
    it moves to the epoch-boundary ``merge_halve`` fold.  (One buffer, not
    separate delta arrays: the single-buffer DUS chain is the shape XLA
    CPU's copy elision already handles in place on the unsharded path —
    separate delta buffers measured 4 full-array copies per access.)

    Mesh mode (``spec.mesh_devices > 0``): dispatched to
    :func:`_sketch_add_mesh` — ``counters``/``dk`` arrive as
    (global, local-delta) tuples inside a shard_map body.
    """
    if spec.mesh_devices:
        return _sketch_add_mesh(spec, params, counters, dk, size, kidx, kdkb)
    # single-word writes are dynamic_update_slice, NOT scatter (.at[].set):
    # XLA CPU updates a loop-carried buffer in place for DUS but lowers the
    # equivalent scatter to a full-array copy, which would put an O(width)
    # copy on every access and sink the capacity-independent set path
    if spec.dk_bits:
        # host _dk_put semantics (a later probe of the same access observes
        # bits set by an earlier one), restructured as ONE gather + straight-
        # line writes: intra-access carry is resolved in-register via pairwise
        # probe comparisons, and duplicate-word writes carry identical merged
        # values.  Interleaving reads between the writes defeats XLA CPU's
        # in-place analysis and costs a full dk copy per read.
        np_ = spec.dk_probes
        w_idx = kdkb >> 5
        bpos = kdkb & 31
        if spec.shards > 1:
            dw_idx = spec.dk_words + w_idx             # delta half (written)
            # barrier: materialize BOTH gathers before any write fusion —
            # a dynamic-slice read fused INTO a later DUS write re-reads
            # the original buffer mid-chain, keeping it live and costing
            # two full copies per access
            words, gwords = _barrier(
                (_ds_gather(dk, dw_idx), _ds_gather(dk, w_idx)))
            eff_words = words | gwords                 # | global half (read)
            # the global-half gather feeds only the LATER counter writes
            # (via the gate), not the dk writes below — anchor it into the
            # first dk write or the scheduler may run it after the write
            # and copy the whole doorkeeper every access (see _sched_dep)
            zdk = _sched_dep(eff_words)
        else:
            dw_idx = w_idx
            if _big_operand(spec.dk_words):
                # unrolled scalar-slice gather + barrier, same discipline
                # as the sharded branch: the fused (dkp,)-element gather is
                # costed by its OPERAND and the parallel task partitioner
                # multithreads it past the cliff — a thread-pool dispatch
                # per access
                words = _barrier(_ds_gather(dk, w_idx))
            else:
                words = dk[w_idx]                      # (dkp,) one gather
            eff_words = words
            zdk = None
        pre = (eff_words >> bpos) & 1
        present = jnp.int32(1)
        for i in range(np_):
            eff = pre[i]
            for j in range(i):                         # set by earlier probe?
                eff = eff | (kdkb[j] == kdkb[i]).astype(jnp.int32)
            present &= eff
        bitm = jnp.int32(1) << bpos
        for i in range(np_):
            merged = words[i] | bitm[i]
            if i == 0 and zdk is not None:
                merged = merged | zdk                  # always 0; see above
            for j in range(np_):
                if j != i:                             # same-word probes merge
                    merged = merged | jnp.where(w_idx[j] == w_idx[i],
                                                bitm[j], 0)
            dk = _ldus1(dk, merged[None], dw_idx[i])
        gate = present.astype(jnp.bool_)   # repeat visitor -> main table
    else:
        gate = jnp.bool_(True)

    flat = _row_offsets(spec) + _word_of(spec, kidx)   # (rows,) word positions
    if spec.shards > 1:
        dflat = spec.counter_words + flat              # delta half (written)
        # barrier: same read-materialization discipline as the doorkeeper
        words, gw = _barrier(
            (_ds_gather(counters, dflat), _ds_gather(counters, flat)))
        # conservative update judges the COMBINED count; the bump lands in
        # the delta field.  bump only fires while the combined min < cap,
        # so every field keeps global+delta <= cap (no overflow, and the
        # merge fold never actually saturates in-engine).  The min runs as
        # an unrolled minimum chain, not a reduce: XLA CPU's parallel task
        # partitioner multithreads small reduce fusions whose fused gathers
        # touch big operands, costing a thread dispatch per access
        vals = (_counter_vals(spec, words, kidx)
                + _counter_vals(spec, gw, kidx))
        m = vals[0]
        for r in range(1, spec.rows):
            m = jnp.minimum(m, vals[r])
    else:
        dflat = flat
        if _big_operand(spec.counter_words):
            # unrolled scalar-slice gather + unrolled minimum chain (not a
            # reduce): the same in-place discipline the sharded path needed
            # — a fused (rows,)-gather over a >= 2^18-counter buffer gets
            # multithreaded by the parallel task partitioner, putting a
            # thread-pool dispatch on every access
            words = _barrier(_ds_gather(counters, flat))
            vals = _counter_vals(spec, words, kidx)
            m = vals[0]
            for r in range(1, spec.rows):
                m = jnp.minimum(m, vals[r])
        else:
            words = counters[flat]
            vals = _counter_vals(spec, words, kidx)
            m = vals.min()
    bump = gate & (m < params[P_CAP])
    sub = kidx & (spec.counters_per_word - 1)
    new = jnp.where(bump & (vals == m),
                    words + (jnp.int32(1) << (sub * spec.counter_bits)), words)
    for r in range(spec.rows):         # rows write disjoint word segments
        counters = _ldus1(counters, new[r][None], dflat[r])

    size = size + 1
    if spec.shards > 1:
        # sharded: aging is deferred to the epoch-boundary merge_halve fold
        # (kernels/sketch_merge.py) — the per-access path never resets
        return counters, dk, size
    with jax.named_scope("reset"):
        do_reset = (params[P_SAMPLE] > 0) & (size >= params[P_SAMPLE])
        # lanes: the dynamic-trip-count word loops would batch into a
        # masked while over PER-LANE trip counts with one scatter per word —
        # the fused masked pass (identical arithmetic) is the scatter-free
        # form, and the small per-tenant sketches of a lane-batched run sit
        # well below the size where the masked pass was ever a problem
        if use_cond and not _LANE_TRACE[0]:
            # dynamic-trip-count word loops: 0 iterations on the (vast
            # majority of) accesses where no reset fires, in-place
            # single-word updates when it does.  Neither lax.cond (copies
            # its big operands on every call) nor a masked where (a full
            # O(width) pass every access) keeps the set path's per-access
            # cost capacity-independent on XLA CPU.
            def halve_one(i, c):
                w = jax.lax.dynamic_slice(c, (i,), (1,))
                return jax.lax.dynamic_update_slice(
                    c, halve_words(w, spec.counter_bits), (i,))

            def zero_one(i, d):
                return jax.lax.dynamic_update_slice(
                    d, jnp.zeros((1,), jnp.int32), (i,))

            counters = jax.lax.fori_loop(
                0, jnp.where(do_reset, counters.shape[0], 0), halve_one,
                counters)
            dk = jax.lax.fori_loop(
                0, jnp.where(do_reset, dk.shape[0], 0), zero_one, dk)
            size = jnp.where(do_reset, size // 2, size)
        else:
            # select, not lax.cond: XLA CPU cond copies its operand buffers
            # every step, which costs more than the fused masked pass it
            # would skip at the flat path's small sketch sizes
            counters = jnp.where(do_reset,
                                 halve_words(counters, spec.counter_bits),
                                 counters)
            dk = jnp.where(do_reset, jnp.zeros_like(dk), dk)
            size = jnp.where(do_reset, size // 2, size)
    return counters, dk, size


def _sketch_add_mesh(spec: StepSpec, params, counters, dk, size, kidx, kdkb):
    """Multi-device twin of the sharded ``_sketch_add`` branch (runs inside
    a ``shard_map`` body over :data:`MESH_AXIS`).

    ``counters`` is a (global ``(counter_words,)``, local delta
    ``(local_shards, rows, wps_shard)``) pair; ``dk`` likewise with the
    local doorkeeper deltas ``(local_shards, dkw_shard)``.  Every device
    runs the identical replicated computation over the replicated global
    halves and cache tables, but a key's delta slice is resident on exactly
    one device (block placement: device ``d`` owns shards
    ``[d*L, (d+1)*L)``), so the masked delta writes are device-local and
    the sketch add needs NO cross-device exchange: the doorkeeper gate and
    the conservative-update bump are consumed only by the owner's writes —
    a non-owner computes don't-care values there and writes nothing.
    Arithmetic is field-for-field the single-device sharded branch, so the
    combined [global || all-gathered deltas] state evolves bit-identically.
    """
    cg, cd = counters
    dkg, dd = dk
    L = spec.local_shards
    ks = kidx[0] // spec.width_shard             # owning shard (rows agree)
    base = jax.lax.axis_index(MESH_AXIS).astype(jnp.int32) * L
    local = (ks >= base) & (ks < base + L)
    lks = jnp.clip(ks - base, 0, L - 1)
    cdf = cd.reshape(-1)
    ddf = dd.reshape(-1)

    if spec.dk_bits:
        np_ = spec.dk_probes
        w_idx = kdkb >> 5                        # global-half word positions
        bpos = kdkb & 31
        # local delta word: shard-major (local shard, word-within-shard)
        ldw = lks * spec.dkw_shard + ((kdkb - ks * spec.dk_bits_shard) >> 5)
        words, gwords = jax.lax.optimization_barrier(
            (_ds_gather(ddf, ldw), _ds_gather(dkg, w_idx)))
        # owner composes delta|global exactly like the single-device branch;
        # a non-owner's `present` is a don't-care (bump writes are masked)
        eff_words = jnp.where(local, words, 0) | gwords
        pre = (eff_words >> bpos) & 1
        present = jnp.int32(1)
        for i in range(np_):
            eff = pre[i]
            for j in range(i):                   # set by an earlier probe?
                eff = eff | (kdkb[j] == kdkb[i]).astype(jnp.int32)
            present &= eff
        bitm = jnp.int32(1) << bpos
        for i in range(np_):
            merged = words[i] | bitm[i]
            for j in range(np_):
                if j != i:                       # same-word probes merge
                    merged = merged | jnp.where(w_idx[j] == w_idx[i],
                                                bitm[j], 0)
            ddf = jax.lax.dynamic_update_slice(
                ddf, jnp.where(local, merged, words[i])[None], (ldw[i],))
        gate = present.astype(jnp.bool_)
    else:
        gate = jnp.bool_(True)

    flat = _row_offsets(spec) + _word_of(spec, kidx)      # global positions
    h = kidx - ks * spec.width_shard             # per-shard probe offsets
    dflat = ((lks * spec.rows + jnp.arange(spec.rows, dtype=jnp.int32))
             * spec.wps_shard + _word_of(spec, h))
    words, gw = jax.lax.optimization_barrier(
        (_ds_gather(cdf, dflat), _ds_gather(cg, flat)))
    vals = (jnp.where(local, _counter_vals(spec, words, kidx), 0)
            + _counter_vals(spec, gw, kidx))
    m = vals[0]
    for r in range(1, spec.rows):
        m = jnp.minimum(m, vals[r])
    bump = gate & (m < params[P_CAP])
    sub = kidx & (spec.counters_per_word - 1)
    new = jnp.where(bump & (vals == m),
                    words + (jnp.int32(1) << (sub * spec.counter_bits)), words)
    for r in range(spec.rows):
        cdf = jax.lax.dynamic_update_slice(
            cdf, jnp.where(local, new[r], words[r])[None], (dflat[r],))
    # aging is deferred to the epoch-boundary all-gather merge_halve fold
    return ((cg, cdf.reshape(cd.shape)), (dkg, ddf.reshape(dd.shape)),
            size + 1)


def _estimate_pair_stale(spec: StepSpec, counters, dk, idx2, dkb2):
    """Mesh twin of the sharded ``_estimate_pair`` branch — speculative
    stale-global admission (``mesh_exchange="stale"``), ZERO cross-device
    exchange.

    Estimates read ONLY the replicated global halves: every device computes
    the identical (replicated) verdict locally, so the cache tables never
    diverge and the per-access path stays collective-free.  The local delta
    — even on the device that owns the entry's shard — is deliberately
    ignored: composing it would make the owner's verdict differ from the
    other devices' and fork the replicated tables.  The estimate is
    therefore stale by at most one merge epoch; the once-per-epoch
    :func:`repro.kernels.sketch_merge.merge_halve_mesh` all-gather
    reconciles it, bounding the hit-ratio deviation to the goldens-±0.01
    tier (tests/test_distributed.py pins this, next to the bit-exact host
    twin ``core.sketch.ShardedFrequencySketch(stale_estimates=True)``).

    This replaced the original per-access 2-int ``psum`` (one collective
    per access — measured 62.8x the single-device sharded cost on the
    forced-2-device bench); the exact path is now the "chunk" mode, which
    never calls the mesh estimator at all.
    """
    cg, _cd = counters
    dkg, _dd = dk
    flat2 = _row_offsets(spec)[None, :] + _word_of(spec, idx2)
    gw = _ds_gather(cg, flat2.reshape(-1)).reshape(2, spec.rows)
    vals = _counter_vals(spec, gw, idx2)
    est = vals[:, 0]
    for r in range(1, spec.rows):
        est = jnp.minimum(est, vals[:, r])
    if spec.dk_bits:
        bb = (dkb2 >> 5).reshape(-1)
        gbits = _ds_gather(dkg, bb).reshape(2, spec.dkp)
        bits = (gbits >> (dkb2 & 31)) & 1
        ok = bits[:, 0]
        for p in range(1, bits.shape[1]):
            ok = ok & bits[:, p]
        est = est + ok
    return est


def _estimate_pair(spec: StepSpec, counters, dk, idx2, dkb2):
    """TinyLFU estimates for two resident entries from their stored probes.

    idx2: (2, rows); dkb2: (2, dkp) -> (2,) int32 estimates.

    Sharded mode: an estimate composes the global half + the delta half of
    the split buffers (each entry's stored probes already point into its
    owning shard's slice).  The row min / doorkeeper all run as unrolled
    chains instead of reduces — XLA CPU's parallel task partitioner
    multithreads reduce fusions whose fused gathers touch the doubled
    buffers, costing a thread-pool dispatch per access (measured 5x).

    The unsharded branch switches to the same discipline (unrolled
    scalar-slice gathers + unrolled reduce chains) once its buffers reach
    ``_big_operand`` (~512KB, width >= 2^18 at default geometry — ROADMAP
    "XLA-CPU cost-model cliffs"); below that the fused gathers are cheaper
    and every pre-cliff program stays byte-identical to the PR 4 one.

    Mesh mode dispatches to :func:`_estimate_pair_stale` — stale-global
    admission, the only per-access estimator that ever runs inside a
    shard_map body (``mesh_exchange="chunk"`` replays the single-device
    program with ``mesh_devices=0``, so it takes the sharded branch here).
    """
    if spec.mesh_devices:
        return _estimate_pair_stale(spec, counters, dk, idx2, dkb2)
    flat2 = _row_offsets(spec)[None, :] + _word_of(spec, idx2)
    ff = flat2.reshape(-1)
    k = ff.shape[0]
    if spec.shards > 1:
        gw = _ds_gather(counters, ff).reshape(2, k // 2)
        dw = _ds_gather(counters, spec.counter_words + ff).reshape(2, k // 2)
        vals = (_counter_vals(spec, gw, idx2)
                + _counter_vals(spec, dw, idx2))
        est = vals[:, 0]
        for r in range(1, spec.rows):
            est = jnp.minimum(est, vals[:, r])
    elif _big_operand(spec.counter_words):
        gw = _ds_gather(counters, ff).reshape(2, k // 2)
        vals = _counter_vals(spec, gw, idx2)
        est = vals[:, 0]
        for r in range(1, spec.rows):
            est = jnp.minimum(est, vals[:, r])
    else:
        vals = _counter_vals(spec, counters[flat2], idx2)
        est = vals.min(axis=-1)
    if spec.dk_bits:
        bb = (dkb2 >> 5).reshape(-1)
        kb = bb.shape[0]
        if spec.shards > 1 or _big_operand(spec.dk_words):
            if spec.shards > 1:
                w2 = (_ds_gather(dk, bb)
                      | _ds_gather(dk, spec.dk_words + bb)).reshape(2,
                                                                    kb // 2)
            else:
                w2 = _ds_gather(dk, bb).reshape(2, kb // 2)
            bits = (w2 >> (dkb2 & 31)) & 1
            ok = bits[:, 0]
            for p in range(1, bits.shape[1]):
                ok = ok & bits[:, p]
            est = est + ok
        else:
            w2 = dk[dkb2 >> 5]
            ok = (((w2 >> (dkb2 & 31)) & 1) == 1).all(axis=-1)
            est = est + ok.astype(jnp.int32)
    return est


def _estimate_block(spec: StepSpec, counters, dk, idxs, dkbs):
    """TinyLFU estimates for K records from their stored probes.

    idxs: (K, rows); dkbs: (K, dkp) -> (K,) int32 estimates.  K-record
    generalization of :func:`_estimate_pair` for the competitor policies
    (the ``"lfu"`` victim scan estimates every record of both choice sets;
    ``"s3fifo"`` estimates the displaced candidate alone).  Competitors
    run unsharded and mesh-free by construction (StepSpec asserts), so
    only the two unsharded disciplines exist: fused fancy-indexing
    gathers below the ``_big_operand`` cliff, unrolled scalar slices +
    unrolled reduce chains past it (same rationale as ``_estimate_pair``).
    """
    k = idxs.shape[0]
    flat = _row_offsets(spec)[None, :] + _word_of(spec, idxs)
    if _big_operand(spec.counter_words):
        gw = _ds_gather(counters, flat.reshape(-1)).reshape(k, spec.rows)
        vals = _counter_vals(spec, gw, idxs)
        est = vals[:, 0]
        for r in range(1, spec.rows):
            est = jnp.minimum(est, vals[:, r])
    else:
        vals = _counter_vals(spec, counters[flat], idxs)
        est = vals.min(axis=-1)
    if spec.dk_bits:
        if _big_operand(spec.dk_words):
            w2 = _ds_gather(dk, (dkbs >> 5).reshape(-1)).reshape(k, spec.dkp)
            bits = (w2 >> (dkbs & 31)) & 1
            ok = bits[:, 0]
            for p in range(1, bits.shape[1]):
                ok = ok & bits[:, p]
            est = est + ok
        else:
            w2 = dk[dkbs >> 5]
            ok = (((w2 >> (dkbs & 31)) & 1) == 1).all(axis=-1)
            est = est + ok.astype(jnp.int32)
    return est


def _one_access_flat(spec: StepSpec, params: jnp.ndarray, state: dict,
                     klo, khi, kidx, kdkb):
    """Advance the full W-TinyLFU state by one access (exact flat tables).

    ``spec.adaptive`` swaps the capacity mechanism: instead of init-time
    padding, the window quota lives in ``regs[R_WQUOTA]`` and resident
    counts (``R_WCOUNT``/``R_MCOUNT``) gate inserts — at quota the argmin
    hides empty slots so the LRU/SLRU victim is displaced exactly as if the
    table were that size.  All adaptive logic is under a static Python
    branch, so ``adaptive=False`` compiles to the identical program.
    """
    regs = state["regs"]
    t = regs[R_T]

    # -- 1. admission.record(key): sketch add + automatic §3.3 reset ---------
    # (sharded: the add writes the delta half only; aging waits for the
    # epoch-boundary merge_halve fold; mesh: global/local-delta pairs)
    if spec.mesh_devices:
        cin = (state["counters"], state["dcounters"])
        din = (state["doorkeeper"], state["ddoorkeeper"])
    else:
        cin, din = state["counters"], state["doorkeeper"]
    counters, dk, size = _sketch_add(spec, params, cin, din, regs[R_SIZE],
                                     kidx, kdkb)

    wlo, whi, wmeta = state["wlo"], state["whi"], state["wmeta"]
    widx, wdkb = state["widx"], state["wdkb"]
    mlo, mhi, mmeta = state["mlo"], state["mhi"], state["mmeta"]
    midx, mdkb = state["midx"], state["mdkb"]

    if spec.adaptive:
        wquota = regs[R_WQUOTA]
        wcount = regs[R_WCOUNT]
        mcount = regs[R_MCOUNT]
        # total capacity is split at runtime: main gets what the window
        # quota leaves; the protected budget keeps the static FRACTION
        # (prot_cap/main_cap scales with the runtime main capacity, and
        # equals params[P_PROT_CAP] exactly when the quota sits at its
        # configured split — the pinned-quota differential tests rely on it)
        mcap_rt = params[P_WINDOW_CAP] + params[P_MAIN_CAP] - wquota
        prot_rt = jnp.maximum(1, mcap_rt * params[P_PROT_CAP]
                              // jnp.maximum(1, params[P_MAIN_CAP]))
        # adaptive stamps are globally unique ACROSS tables (window even,
        # main odd): one access can stamp both tables (window insert +
        # candidate admit), and the rebalance later migrates window records
        # into main — colliding stamps there would leave victim selection
        # to slot-index tie-breaks no host twin can mirror.  Within a
        # table the 2t/2t+1 mapping preserves every ordering, so a pinned
        # quota still reproduces the static path's hit sequence exactly.
        wst = t + t
        mst = t + t + 1
    else:
        prot_rt = params[P_PROT_CAP]
        wst = t
        mst = t

    # -- 2. lookups (meta >= 0 <=> resident; padding slots hold sentinel key)
    eqw = (wlo == klo) & (whi == khi)
    eqm = (mlo == klo) & (mhi == khi)
    jw = jnp.argmax(eqw)
    jm = jnp.argmax(eqm)
    if _LANE_TRACE[0]:
        # a key occupies at most one slot per table (inserts fire only on
        # miss), so the gather-at-argmax hit test collapses to a reduction
        # over the already-materialized equality mask — each scalar gather
        # op in the batched program breaks the fused elementwise chain and
        # its fixed dispatch cost dominates the small-tenant lane step
        hit_w = jnp.any(eqw & (wmeta >= 0))
        hit_m = jnp.any(eqm & (mmeta >= 0))
        promote = hit_m & jnp.any(eqm & (mmeta >= 0) & (mmeta < _PROT))
    else:
        hit_w = (wlo[jw] == klo) & (whi[jw] == khi) & (wmeta[jw] >= 0)
        hit_m = (mlo[jm] == klo) & (mhi[jm] == khi) & (mmeta[jm] >= 0)
        promote = hit_m & (mmeta[jm] < _PROT)
    hit = hit_w | hit_m

    # -- 3a. window hit: refresh LRU stamp -----------------------------------
    wmeta = _lset(wmeta, jw, wst, hit_w)

    # -- 3b. main hit: SLRU promote-or-refresh -> protected MRU --------------
    mmeta = _lset(mmeta, jm, _PROT | mst, hit_m)
    pcount = regs[R_PCOUNT] + promote.astype(jnp.int32)
    # protected overflow -> demote its LRU entry back to probation MRU.
    # Adaptive: a rebalance can shrink the runtime budget below the resident
    # protected count, so the drain is gated on a main hit (one demotion per
    # promote-or-refresh, like the host twin) — draining on every access
    # would stamp a demotion at t in the same access that inserts a
    # window-displaced candidate at t, breaking stamp uniqueness.  In the
    # static path over implies a promote just happened (the budget is
    # constant), so the gate is vacuous there and the branch keeps the
    # compiled program identical.
    if spec.adaptive:
        over = hit_m & (pcount > prot_rt)
    else:
        over = pcount > prot_rt
    kd = jnp.argmin(jnp.where(mmeta >= _PROT, mmeta, _I32_MAX))
    mmeta = _lset(mmeta, kd, mst, over)
    pcount = pcount - over.astype(jnp.int32)

    # -- 4. miss: insert into window; LRU overflow asks admission ------------
    miss = ~hit
    # argmin(wmeta): empty (-1) before LRU stamps; padding (+MAX) never picked
    if spec.adaptive:
        # at quota, hide the (statically unpadded) empty slots so the argmin
        # lands on the LRU resident — the runtime equivalent of padding
        at_wcap = wcount >= wquota
        ws = jnp.argmin(jnp.where(at_wcap & (wmeta == _EMPTY), _I32_MAX,
                                  wmeta))
    else:
        ws = jnp.argmin(wmeta)
    if _LANE_TRACE[0] and not spec.adaptive:
        # ws == argmin(wmeta), so the gathered value IS the min — the
        # reduction reuses the argmin's input and saves a gather op
        wsmeta = wmeta.min()
    else:
        wsmeta = wmeta[ws]
    push = miss & (wsmeta >= 0)                 # evicting a resident entry
    if spec.adaptive:                           # R_WCOUNT bookkeeping
        w_filled = miss & (wsmeta == _EMPTY)
    cand_lo, cand_hi = wlo[ws], whi[ws]
    cand_idx, cand_dkb = widx[ws], wdkb[ws]
    wlo = _lset(wlo, ws, klo, miss)
    whi = _lset(whi, ws, khi, miss)
    wmeta = _lset(wmeta, ws, wst, miss)
    widx = _lset_row(widx, ws, kidx, miss)
    wdkb = _lset_row(wdkb, ws, kdkb, miss)

    # single argmin = free slot < probation LRU < protected LRU (exact SLRU
    # victim priority); padding (+MAX) is unreachable
    if spec.adaptive:
        at_mcap = mcount >= mcap_rt
        tslot = jnp.argmin(jnp.where(at_mcap & (mmeta == _EMPTY), _I32_MAX,
                                     mmeta))
    else:
        tslot = jnp.argmin(mmeta)
    if _LANE_TRACE[0] and not spec.adaptive:
        vmeta = mmeta.min()                     # == mmeta[argmin(mmeta)]
    else:
        vmeta = mmeta[tslot]
    m_free = vmeta < 0
    # fused TinyLFU verdict from stored probes (post-record sketch state)
    est = _estimate_pair(spec, counters, dk,
                         jnp.stack([cand_idx, midx[tslot]]),
                         jnp.stack([cand_dkb, mdkb[tslot]]))
    admit = est[0] > est[1]
    do_ins = push & (m_free | admit)
    mlo = _lset(mlo, tslot, cand_lo, do_ins)
    mhi = _lset(mhi, tslot, cand_hi, do_ins)
    mmeta = _lset(mmeta, tslot, mst, do_ins)
    midx = _lset_row(midx, tslot, cand_idx, do_ins)
    mdkb = _lset_row(mdkb, tslot, cand_dkb, do_ins)
    pcount = pcount - (do_ins & (vmeta >= _PROT)).astype(jnp.int32)

    # -- 5. bookkeeping ------------------------------------------------------
    counted = (hit & (t >= params[P_WARMUP])).astype(jnp.int32)
    if spec.adaptive:
        regs = jnp.stack([size, pcount, t + 1, regs[R_HITS] + counted,
                          wquota,
                          wcount + w_filled.astype(jnp.int32),
                          mcount + (do_ins & m_free).astype(jnp.int32),
                          regs[R_EHITS] + hit.astype(jnp.int32)])
    else:
        regs = jnp.stack([size, pcount, t + 1, regs[R_HITS] + counted,
                          regs[4], regs[5], regs[6], regs[7]])
    if spec.mesh_devices:
        (cg, cd), (dkg, dd) = counters, dk
        sketch = {"counters": cg, "doorkeeper": dkg,
                  "dcounters": cd, "ddoorkeeper": dd}
    else:
        sketch = {"counters": counters, "doorkeeper": dk}
    # {**state, ...} first: access-invariant keys (e.g. the "csum" integrity
    # vector, touched only by the epoch-boundary merge fold) ride through the
    # scan carry unchanged
    new_state = {**state, **sketch,
                 "wlo": wlo, "whi": whi, "wmeta": wmeta,
                 "widx": widx, "wdkb": wdkb,
                 "mlo": mlo, "mhi": mhi, "mmeta": mmeta,
                 "midx": midx, "mdkb": mdkb, "regs": regs}
    return new_state, hit.astype(jnp.int32)


def _sched_dep(x: jnp.ndarray) -> jnp.ndarray:
    """A data-dependent int32 scalar that is always 0 but opaque to XLA.

    (d >> 31) & (~d >> 31) is zero for every d, yet XLA's simplifier cannot
    prove it.  OR-ing this into the FIRST dynamic_update_slice of a
    loop-carried table forces every computation that read the pre-write
    table to transitively feed that write, so the scheduler runs all reads
    first and the write happens in place.  Without it, XLA CPU may schedule
    an independent read-fusion (e.g. a lookup reduce consumed only by a
    later write) after the first write and must then copy the WHOLE table
    every access — turning the O(ways) step back into O(capacity).
    """
    d = x.reshape(-1)[0]
    return (d >> 31) & ((~d) >> 31)


def _one_access_set(spec: StepSpec, params: jnp.ndarray, state: dict,
                    klo, khi, kidx, kdkb, kwset, kmset, krec):
    """One access against W-way set-associative tables: every table touch is
    a contiguous (assoc, cols) gather + reduce — O(ways), capacity-free.
    ``krec`` is the access's packed window record (:func:`access_records`).

    The main table uses power-of-two-choices placement: a key may reside in
    either of its two hashed sets (lookups probe both); a displaced window
    candidate is admitted against the weakest of its two sets' 2*ways
    records, which both balances set load and doubles the victim pool —
    together this recovers most of the exact global-SLRU hit ratio.

    Dataflow discipline: ALL gathers read the pre-access tables up front;
    aliasing between the key's sets and the candidate's sets is composed
    with selects; the writes go last, with :func:`_sched_dep` anchoring
    every read before the first write so the tables update in place.
    """
    A = spec.assoc
    rows, dkp = spec.rows, spec.dkp
    regs = state["regs"]
    t = regs[R_T]

    # -- 1. admission.record(key): sketch add + amortized in-place reset -----
    # (sharded: the add writes the delta half only; no per-access reset —
    # aging happens in the epoch-boundary merge_halve fold; mesh:
    # global/local-delta pairs)
    with jax.named_scope("sketch"):
        if spec.mesh_devices:
            cin = (state["counters"], state["dcounters"])
            din = (state["doorkeeper"], state["ddoorkeeper"])
        else:
            cin, din = state["counters"], state["doorkeeper"]
        counters, dk, size = _sketch_add(spec, params, cin, din, regs[R_SIZE],
                                         kidx, kdkb, use_cond=True)

    wtab, mtab = state["wtab"], state["mtab"]
    km1, km2 = kmset[0], kmset[1]
    same_km = km2 == km1

    if spec.adaptive:
        # runtime window quota: per-set usable ways come from the ``wuw``
        # state vector, refreshed by each epoch rebalance — uniform
        # (core.hashing.set_ways: first quota % n_sets sets keep one extra
        # way) while quota >= n_sets, load-aware below it (the quota's ways
        # go to the sets with the highest window traffic last epoch —
        # core.adaptive.window_set_ways), so small quotas no longer starve
        # hot sets.  Ways at or beyond a set's usable count READ as padding
        # (_I32_MAX) for every decision; the epoch rebalance keeps them
        # EMPTY in storage, so the write-back restores _EMPTY bit-exactly.
        wquota = regs[R_WQUOTA]
        mcap_rt = params[P_WINDOW_CAP] + params[P_MAIN_CAP] - wquota
        nws, nms = spec.window_sets, spec.main_sets
        way_ids = jnp.arange(A, dtype=jnp.int32)
        wuw = state["wuw"]

        def w_usable(s):
            return jax.lax.dynamic_slice(wuw, (s,), (1,))[0]

        def m_usable(s):
            return mcap_rt // nms + (s < mcap_rt % nms).astype(jnp.int32)

        def mask_ways(blk, u, col):
            return _lset_col(blk, col,
                             jnp.where(way_ids >= u, _I32_MAX, blk[:, col]))

        def unmask_ways(blk, u, col):
            return _lset_col(blk, col,
                             jnp.where(way_ids >= u, _EMPTY, blk[:, col]))
        # globally unique stamps across tables (window even, main odd):
        # see _one_access_flat — the rebalance migrates window records
        # into main, where a stamp collision would leave victim
        # selection to way-index tie-breaks
        wst = t + t
        mst = t + t + 1
    else:
        def mask_ways(blk, u, col):
            return blk

        def unmask_ways(blk, u, col):
            return blk

        def w_usable(s):
            return None

        def m_usable(s):
            return None
        wst = t
        mst = t

    # -- 2. lookups: the key's window set and both main choice sets ----------
    with jax.named_scope("lookup"):
        wblk = mask_ways(
            _set_block(wtab, kwset, A, spec.wcols),
            w_usable(kwset), WT_META)
        wmeta = wblk[:, WT_META]
        match_w = ((wblk[:, WT_LO] == klo) & (wblk[:, WT_HI] == khi)
                   & (wmeta >= 0))
        hit_w = match_w.any()
        jw = jnp.argmax(match_w)

        mblk1 = mask_ways(
            _set_block(mtab, km1, A, spec.mcols),
            m_usable(km1), MT_META)
        mblk2 = mask_ways(
            _set_block(mtab, km2, A, spec.mcols),
            m_usable(km2), MT_META)

        def match_in(blk):
            return ((blk[:, MT_LO] == klo) & (blk[:, MT_HI] == khi)
                    & (blk[:, MT_META] >= 0))

        match1 = match_in(mblk1)
        match2 = match_in(mblk2) & ~same_km  # aliased choices: set1 only
        hit1 = match1.any()
        hit2 = match2.any()
        hit_m = hit1 | hit2
        hit = hit_w | hit_m

    # -- 3a. window hit/miss: refresh stamp, insert on miss (not yet written)
    with jax.named_scope("window"):
        wmeta = _lset(wmeta, jw, wst, hit_w)
        miss = ~hit
        ws = jnp.argmin(wmeta)
        newrow = _with_meta(krec, wst)
        # padding (+MAX) can only win the argmin in a zero-way set (vmapped
        # sweeps far below the shared geometry, or degenerate tiny windows):
        # such an access bypasses the window — the incoming key itself
        # becomes the admission candidate, exactly like the host twin's
        # insert-then-immediately-displace
        w_ok = wmeta[ws] != _I32_MAX
        push = miss & ((wmeta[ws] >= 0) | ~w_ok)
        cand = jnp.where(w_ok, wblk[ws], newrow)    # full packed record
        wblk = _lset_col(wblk, WT_META, wmeta)
        wblk = _lset_row(wblk, ws, newrow, miss & w_ok)

    # -- 3b. main hit: SLRU promote-or-refresh within the RESIDENT set -------
    with jax.named_scope("slru"):
        def hit_update(blk, match, hit_half):
            meta = blk[:, MT_META]
            j = jnp.argmax(match)
            meta = _lset(meta, j, _PROT | mst, hit_half)
            # the set's protected budget scales its usable ways by the global
            # protected fraction; counting resident protected beats carrying a
            # per-set register (padding meta +MAX excluded: stamps < 2^31-1)
            usable = (meta != _I32_MAX).sum()
            nprot = ((meta >= _PROT) & (meta != _I32_MAX)).sum()
            cap = jnp.maximum(1, usable * params[P_PROT_CAP]
                              // jnp.maximum(1, params[P_MAIN_CAP]))
            over = hit_half & (nprot > cap)
            kd = jnp.argmin(jnp.where(meta >= _PROT, meta, _I32_MAX))
            meta = _lset(meta, kd, mst, over)
            return _lset_col(blk, MT_META, meta)

        mblk1u = hit_update(mblk1, match1, hit1)
        mblk2u = hit_update(mblk2, match2, hit2)
        m2eff = jnp.where(same_km, mblk1u, mblk2u)  # aliased sets follow set1

    # -- 4. admission: candidate vs the weakest of its 2*ways records --------
    # the candidate's choice sets were stored at its window insert; they are
    # gathered from the PRE-access table, then the hit updates above are
    # replayed onto them wherever the sets alias
    with jax.named_scope("admission"):
        c1, c2 = cand[WT_MSET], cand[WT_MSET2]
        same_c = c2 == c1

        def fixup(cb, c):
            return jnp.where(c == km2, m2eff, jnp.where(c == km1, mblk1u, cb))

        cb1 = fixup(mask_ways(
            _set_block(mtab, c1, A, spec.mcols),
            m_usable(c1), MT_META), c1)
        cb2 = fixup(mask_ways(
            _set_block(mtab, c2, A, spec.mcols),
            m_usable(c2), MT_META), c2)
        cblk = jnp.concatenate([cb1, cb2], axis=0)          # (2A, cols)
        # argmin = empty < probation LRU < protected LRU across both sets;
        # ties pick the first half, so aliased choice sets stay consistent
        tslot = jnp.argmin(cblk[:, MT_META])
        vic = cblk[tslot]
        m_free = vic[MT_META] < 0
        est = _estimate_pair(
            spec, counters, dk,
            jnp.stack([cand[5:5 + rows], vic[3:3 + rows]]),
            jnp.stack([cand[5 + rows:5 + rows + dkp],
                       vic[3 + rows:3 + rows + dkp]]))
        admit = est[0] > est[1]
        # all-padding candidate sets (see w_ok above) never accept an insert
        do_ins = push & (vic[MT_META] != _I32_MAX) & (m_free | admit)
        candrow = _main_record(_with_meta(cand, mst))
        in1 = do_ins & (tslot < A)
        in2 = do_ins & (tslot >= A)
        j1 = jnp.minimum(tslot, A - 1)
        j2 = jnp.clip(tslot - A, 0, A - 1)
        cb1u = _lset_row(cb1, j1, candrow, in1)
        cb2u = _lset_row(cb2, j2, candrow, in2)
        cb2u = jnp.where(same_c, cb1u, cb2u)

    # -- 5. writes last; later writes win where the four sets alias ----------
    # (adaptive: masked ways are restored to EMPTY before the write — the
    # decisions above never touched them, and storage must stay quota-free)
    with jax.named_scope("writes"):
        mblk1u = unmask_ways(mblk1u, m_usable(km1), MT_META)
        m2eff = unmask_ways(m2eff, m_usable(km2), MT_META)
        cb1u = unmask_ways(cb1u, m_usable(c1), MT_META)
        cb2u = unmask_ways(cb2u, m_usable(c2), MT_META)
        wblk = unmask_ways(wblk, w_usable(kwset), WT_META)
        zm = _sched_dep(mblk2u) | _sched_dep(cb1u) | _sched_dep(cb2u)
        mtab = _ldus_block(mtab, mblk1u | zm, km1)
        mtab = _ldus_block(mtab, m2eff, km2)
        mtab = _ldus_block(mtab, cb1u, c1)
        mtab = _ldus_block(mtab, cb2u, c2)
        zw = _sched_dep(mtab)       # after every main write: covers all reads
        wtab = _ldus_block(wtab, wblk | zw, kwset)

    # -- 6. bookkeeping (R_PCOUNT is unused: protected counts are per-set) ---
    with jax.named_scope("bookkeeping"):
        counted = (hit & (t >= params[P_WARMUP])).astype(jnp.int32)
        if spec.adaptive:
            # per-set window-traffic telemetry feeding the next rebalance's
            # load-aware quota distribution (single-word DUS, O(1) per access)
            wsl = state["wsl"]
            lcur = jax.lax.dynamic_slice(wsl, (kwset,), (1,))
            wsl = _ldus1(wsl, lcur + 1, kwset)
            new_regs = [size, regs[R_PCOUNT], t + 1, regs[R_HITS] + counted,
                        wquota, regs[5], regs[6],
                        regs[R_EHITS] + hit.astype(jnp.int32)]
        else:
            new_regs = [size, regs[R_PCOUNT], t + 1, regs[R_HITS] + counted,
                        regs[4], regs[5], regs[6], regs[7]]
        if spec.events:
            # a candidate met a resident victim: the estimate decided it
            contest = push & (vic[MT_META] != _I32_MAX) & ~m_free
            # one vector add for the four counters: scalar adds per counter
            # cost the TPU's scalar unit more than one small fusion; the
            # reset halves the count, so size is no longer size + 1
            inc = jnp.stack([push, contest & admit, contest & ~admit,
                             size != regs[R_SIZE] + 1]).astype(jnp.int32)
            regs = jnp.concatenate([jnp.stack(new_regs),
                                    regs[R_CANDS:NREGS_EVENTS] + inc])
        else:
            regs = jnp.stack(new_regs)
    if spec.mesh_devices:
        (cg, cd), (dkg, dd) = counters, dk
        sketch = {"counters": cg, "doorkeeper": dkg,
                  "dcounters": cd, "ddoorkeeper": dd}
    else:
        sketch = {"counters": counters, "doorkeeper": dk}
    new_state = {**state, **sketch, "wtab": wtab, "mtab": mtab, "regs": regs}
    if spec.adaptive:
        new_state["wsl"] = wsl
        new_state["wuw"] = wuw
    return new_state, hit.astype(jnp.int32)


def _one_access_set_s3fifo(spec: StepSpec, params: jnp.ndarray, state: dict,
                           klo, khi, kidx, kdkb, kwset, kmset, krec):
    """One access under the ``"s3fifo"`` competitor policy.

    S3-FIFO (SNIPPETS.md / CacheKit competitor set) on the shared
    set-associative machinery: the window table is the *small* FIFO
    (insert-stamp order, NO stamp refresh on hit — a window hit leaves the
    table untouched), the main table is the CLOCK-marked *main* FIFO (a
    hit ORs ``_PROT`` into the meta as the accessed bit, keeping the
    insert stamp, so the victim argmin is empty < unmarked-oldest <
    marked-oldest), and the one-hit-wonder filter is the frequency sketch
    itself: a candidate displaced from the small FIFO enters main only if
    its estimate is >= 2 (with the doorkeeper on, exactly "seen more than
    once"), with NO free-slot override — one-hit wonders never enter main.
    S3-FIFO's ghost queue is approximated by that sketch memory rather
    than tracked exactly (documented in ARCHITECTURE.md).
    """
    A = spec.assoc
    rows, dkp = spec.rows, spec.dkp
    regs = state["regs"]
    t = regs[R_T]

    counters, dk, size = _sketch_add(spec, params, state["counters"],
                                     state["doorkeeper"], regs[R_SIZE],
                                     kidx, kdkb, use_cond=True)

    wtab, mtab = state["wtab"], state["mtab"]
    km1, km2 = kmset[0], kmset[1]
    same_km = km2 == km1

    # -- lookups: small-FIFO set and both main choice sets -------------------
    wblk = _set_block(wtab, kwset, A, spec.wcols)
    wmeta = wblk[:, WT_META]
    match_w = (wblk[:, WT_LO] == klo) & (wblk[:, WT_HI] == khi) & (wmeta >= 0)
    hit_w = match_w.any()

    mblk1 = _set_block(mtab, km1, A, spec.mcols)
    mblk2 = _set_block(mtab, km2, A, spec.mcols)

    def match_in(blk):
        return ((blk[:, MT_LO] == klo) & (blk[:, MT_HI] == khi)
                & (blk[:, MT_META] >= 0))

    match1 = match_in(mblk1)
    match2 = match_in(mblk2) & ~same_km     # aliased choices: count set1 only
    hit = hit_w | match1.any() | match2.any()

    # -- small-FIFO miss insert (hit: NO write — FIFO order is insert order) -
    miss = ~hit
    ws = jnp.argmin(wmeta)                  # oldest insert stamp (or empty)
    newrow = _with_meta(krec, t)
    w_ok = wmeta[ws] != _I32_MAX            # zero-way window set: bypass
    push = miss & ((wmeta[ws] >= 0) | ~w_ok)
    cand = jnp.where(w_ok, wblk[ws], newrow)
    wblk = _lset_row(wblk, ws, newrow, miss & w_ok)

    # -- main hit: set the CLOCK accessed bit, keep the insert stamp ---------
    def mark(blk, match):
        meta = blk[:, MT_META]
        return _lset_col(blk, MT_META,
                         jnp.where(match, meta | _PROT, meta))

    mblk1u = mark(mblk1, match1)
    mblk2u = mark(mblk2, match2)
    m2eff = jnp.where(same_km, mblk1u, mblk2u)

    # -- admission: sketch-filtered FIFO insert over the candidate's sets ----
    c1, c2 = cand[WT_MSET], cand[WT_MSET2]
    same_c = c2 == c1

    def fixup(cb, c):
        return jnp.where(c == km2, m2eff, jnp.where(c == km1, mblk1u, cb))

    cb1 = fixup(_set_block(mtab, c1, A, spec.mcols), c1)
    cb2 = fixup(_set_block(mtab, c2, A, spec.mcols), c2)
    cblk = jnp.concatenate([cb1, cb2], axis=0)          # (2A, cols)
    tslot = jnp.argmin(cblk[:, MT_META])    # empty < unmarked < marked FIFO
    vic = cblk[tslot]
    est = _estimate_block(spec, counters, dk,
                          cand[5:5 + rows][None, :],
                          cand[5 + rows:5 + rows + dkp][None, :])
    admit = est[0] >= 2                     # one-hit-wonder filter, strict
    do_ins = push & (vic[MT_META] != _I32_MAX) & admit
    candrow = _main_record(_with_meta(cand, t))
    in1 = do_ins & (tslot < A)
    in2 = do_ins & (tslot >= A)
    j1 = jnp.minimum(tslot, A - 1)
    j2 = jnp.clip(tslot - A, 0, A - 1)
    cb1u = _lset_row(cb1, j1, candrow, in1)
    cb2u = _lset_row(cb2, j2, candrow, in2)
    cb2u = jnp.where(same_c, cb1u, cb2u)

    # -- writes last (same aliasing/scheduling discipline as wtinylfu) -------
    zm = _sched_dep(mblk2u) | _sched_dep(cb1u) | _sched_dep(cb2u)
    mtab = _ldus_block(mtab, mblk1u | zm, km1)
    mtab = _ldus_block(mtab, m2eff, km2)
    mtab = _ldus_block(mtab, cb1u, c1)
    mtab = _ldus_block(mtab, cb2u, c2)
    zw = _sched_dep(mtab)       # after every main write: covers all reads
    wtab = _ldus_block(wtab, wblk | zw, kwset)

    counted = (hit & (t >= params[P_WARMUP])).astype(jnp.int32)
    regs = jnp.stack([size, regs[R_PCOUNT], t + 1, regs[R_HITS] + counted,
                      regs[4], regs[5], regs[6], regs[7]])
    new_state = {**state, "counters": counters, "doorkeeper": dk,
                 "wtab": wtab, "mtab": mtab, "regs": regs}
    return new_state, hit.astype(jnp.int32)


def _one_access_set_arc(spec: StepSpec, params: jnp.ndarray, state: dict,
                        klo, khi, kidx, kdkb, kwset, kmset, krec):
    """One access under the ``"arc"`` competitor policy.

    ARC (seed ``core.policies.ARC`` is the reference twin) on the shared
    main table: T1 (recency, probation meta) and T2 (frequency,
    ``_PROT``-tagged meta) share the set-associative table; the adaptive
    target ``p`` lives in the ``R_WQUOTA`` register exactly like the
    adaptive window quota does.  The B1/B2 ghost lists are Bloom halves
    of the dedicated ``"ghost"`` state buffer (``dk_words`` words each,
    addressed by the key's stored doorkeeper probes): membership is
    approximate, removal is wholesale — when a half has absorbed
    ``P_MAIN_CAP`` evictions it is cleared and restarted (the clear is a
    where-gated fori-loop of single-word updates, O(1) amortized — same
    pattern as the §3.3 sketch reset).  The frequency sketch itself is
    NOT consulted (no ``_sketch_add``): ARC is a sketch-free policy and
    rides through with counters/doorkeeper untouched.  The window table
    is bypassed entirely (window_cap collapses to its 1-slot minimum).
    Register map: p -> R_WQUOTA, |T1| -> R_WCOUNT, B1/B2 insert counts ->
    R_MCOUNT / R_EHITS.
    """
    A = spec.assoc
    rows, dkp = spec.rows, spec.dkp
    regs = state["regs"]
    t = regs[R_T]
    p = regs[R_WQUOTA]
    t1count = regs[R_WCOUNT]
    gb1count = regs[R_MCOUNT]
    gb2count = regs[R_EHITS]
    ghost = state["ghost"]
    mtab = state["mtab"]
    km1, km2 = kmset[0], kmset[1]
    same_km = km2 == km1
    mst = t

    # -- lookups (all reads first: choice sets + both ghost Bloom halves) ----
    mblk1 = _set_block(mtab, km1, A, spec.mcols)
    mblk2 = _set_block(mtab, km2, A, spec.mcols)

    def match_in(blk):
        return ((blk[:, MT_LO] == klo) & (blk[:, MT_HI] == khi)
                & (blk[:, MT_META] >= 0))

    match1 = match_in(mblk1)
    match2 = match_in(mblk2) & ~same_km
    hit = match1.any() | match2.any()
    hit_t1 = ((match1 & (mblk1[:, MT_META] < _PROT)).any()
              | (match2 & (mblk2[:, MT_META] < _PROT)).any())

    gpos = kdkb >> 5
    gbit = kdkb & 31
    if _big_operand(2 * spec.dk_words):
        w1 = _ds_gather(ghost, gpos)
        w2 = _ds_gather(ghost, spec.dk_words + gpos)
    else:
        w1 = ghost[gpos]
        w2 = ghost[spec.dk_words + gpos]
    gb1 = (((w1 >> gbit) & 1) == 1).all()
    gb2 = (((w2 >> gbit) & 1) == 1).all()

    # -- hit: promote to T2 MRU (both lists; a T1 hit shrinks |T1|) ----------
    def promote(blk, match):
        meta = blk[:, MT_META]
        return _lset_col(blk, MT_META,
                         jnp.where(match, _PROT | mst, meta))

    mblk1u = promote(mblk1, match1)
    mblk2u = promote(mblk2, match2)
    m2eff = jnp.where(same_km, mblk1u, mblk2u)

    # -- miss: ghost-driven delta=1 adaptation of the target p ---------------
    miss = ~hit
    in_b1 = miss & gb1
    in_b2 = miss & gb2 & ~gb1
    p_new = jnp.where(in_b1, jnp.minimum(params[P_MAIN_CAP], p + 1),
                      jnp.where(in_b2, jnp.maximum(0, p - 1), p))

    # -- REPLACE: prefer the T1 LRU while |T1| exceeds p (seed-ARC tiebreak:
    # a B2 ghost hit also evicts from T1 at |T1| == p); XOR-flipping _PROT
    # into the order key swaps which list the shared argmin prefers, and
    # degrades gracefully to the other list when the preferred one has no
    # record in these two sets
    cblk = jnp.concatenate([mblk1u, m2eff], axis=0)     # (2A, cols)
    meta_c = cblk[:, MT_META]
    prefer_t1 = (t1count > p_new) | (in_b2 & (t1count == p_new))
    flip = jnp.where(prefer_t1, 0, _PROT)
    okey = jnp.where(meta_c == _I32_MAX, _I32_MAX,
                     jnp.where(meta_c < 0, -1, meta_c ^ flip))
    tslot = jnp.argmin(okey)
    vic = cblk[tslot]
    m_free = vic[MT_META] < 0
    do_ins = miss & (okey[tslot] != _I32_MAX)           # always admit
    evict = do_ins & ~m_free
    vic_was_t1 = evict & (vic[MT_META] < _PROT)

    # -- ghost maintenance: evicted key's stored dk probes enter B1/B2 -------
    goff = jnp.where(vic_was_t1, 0, spec.dk_words)
    vdkb = vic[3 + rows:3 + rows + dkp]
    vpos = goff + (vdkb >> 5)
    vbit = jnp.int32(1) << (vdkb & 31)
    gw = _ds_gather(ghost, vpos)            # pre-write read (see below)
    clr1 = vic_was_t1 & (gb1count >= params[P_MAIN_CAP])
    clr2 = evict & ~vic_was_t1 & (gb2count >= params[P_MAIN_CAP])
    clr = clr1 | clr2
    # anchor every ghost read before the first ghost write (in-place DUS
    # discipline — the query gathers feed only p_new/regs otherwise)
    zg = _sched_dep(w1) | _sched_dep(w2) | _sched_dep(gw)
    if not _LANE_TRACE[0]:
        # saturation clear: where-gated trip count, 0 iterations on the
        # (vast majority of) accesses where no clear fires — the same
        # O(1)-amortized pattern as the use_cond sketch reset
        def zero_one_g(i, g):
            return jax.lax.dynamic_update_slice(
                g, jnp.zeros((1,), jnp.int32) | zg, (goff + i,))

        ghost = jax.lax.fori_loop(
            0, jnp.where(clr, spec.dk_words, 0), zero_one_g, ghost)
    else:
        giota = jnp.arange(2 * spec.dk_words, dtype=jnp.int32)
        inhalf = jnp.where(vic_was_t1, giota < spec.dk_words,
                           giota >= spec.dk_words)
        ghost = jnp.where(clr & inhalf, 0, ghost)
    # bit inserts: same-word probes merge in-register (see _sketch_add);
    # a cleared half contributes zeros regardless of the pre-clear read
    base = jnp.where(clr, 0, gw)
    for i in range(dkp):
        merged = base[i] | vbit[i]
        if i == 0:
            merged = merged | zg
        for j in range(dkp):
            if j != i:
                merged = merged | jnp.where(vpos[j] == vpos[i], vbit[j], 0)
        ghost = _ldus1(ghost, jnp.where(evict, merged, gw[i])[None], vpos[i])
    gb1c = jnp.where(clr1, 0, gb1count) + vic_was_t1.astype(jnp.int32)
    gb2c = (jnp.where(clr2, 0, gb2count)
            + (evict & ~vic_was_t1).astype(jnp.int32))

    # -- insert: ghost-remembered keys go to T2, fresh keys to T1 MRU --------
    meta0 = jnp.where(gb1 | gb2, _PROT | mst, mst)
    candrow = _main_record(_with_meta(krec, meta0))
    in1 = do_ins & (tslot < A)
    in2 = do_ins & (tslot >= A)
    j1 = jnp.minimum(tslot, A - 1)
    j2 = jnp.clip(tslot - A, 0, A - 1)
    mb1f = _lset_row(mblk1u, j1, candrow, in1)
    mb2f = _lset_row(m2eff, j2, candrow, in2)
    mb2f = jnp.where(same_km, mb1f, mb2f)
    t1c = (t1count - hit_t1.astype(jnp.int32)
           - vic_was_t1.astype(jnp.int32)
           + (do_ins & (meta0 < _PROT)).astype(jnp.int32))

    # -- writes last ---------------------------------------------------------
    zm = _sched_dep(mb2f)
    mtab = _ldus_block(mtab, mb1f | zm, km1)
    mtab = _ldus_block(mtab, mb2f, km2)

    counted = (hit & (t >= params[P_WARMUP])).astype(jnp.int32)
    regs = jnp.stack([regs[R_SIZE], regs[R_PCOUNT], t + 1,
                      regs[R_HITS] + counted, p_new, t1c, gb1c, gb2c])
    new_state = {**state, "mtab": mtab, "ghost": ghost, "regs": regs}
    return new_state, hit.astype(jnp.int32)


def _one_access_set_lfu(spec: StepSpec, params: jnp.ndarray, state: dict,
                        klo, khi, kidx, kdkb, kwset, kmset, krec):
    """One access under the ``"lfu"`` competitor policy.

    Heap-free sketch-LFU (Shah/Mitra/Matani's O(1) LFU, mapped onto the
    packed-record layout): there is no frequency heap at all — every
    record's stored sketch probes make the per-set gather+reduce itself
    the min-frequency scan, O(ways) per access like everything else.  No
    window (window_cap collapses to its 1-slot minimum), no admission
    filter (always admit: plain LFU has no ghost/doorkeeper gate), victim
    = the resident with the smallest sketch estimate across the key's two
    choice sets, stamps breaking frequency ties toward the LRU record.
    A hit refreshes the stamp (probation meta only — no ``_PROT`` tier).
    """
    A = spec.assoc
    rows, dkp = spec.rows, spec.dkp
    regs = state["regs"]
    t = regs[R_T]
    mst = t

    counters, dk, size = _sketch_add(spec, params, state["counters"],
                                     state["doorkeeper"], regs[R_SIZE],
                                     kidx, kdkb, use_cond=True)

    mtab = state["mtab"]
    km1, km2 = kmset[0], kmset[1]
    same_km = km2 == km1

    mblk1 = _set_block(mtab, km1, A, spec.mcols)
    mblk2 = _set_block(mtab, km2, A, spec.mcols)

    def match_in(blk):
        return ((blk[:, MT_LO] == klo) & (blk[:, MT_HI] == khi)
                & (blk[:, MT_META] >= 0))

    match1 = match_in(mblk1)
    match2 = match_in(mblk2) & ~same_km
    hit = match1.any() | match2.any()

    def refresh(blk, match):
        meta = blk[:, MT_META]
        return _lset_col(blk, MT_META, jnp.where(match, mst, meta))

    mblk1u = refresh(mblk1, match1)
    mblk2u = refresh(mblk2, match2)
    m2eff = jnp.where(same_km, mblk1u, mblk2u)

    # -- victim: min sketch estimate over both sets, stamp-LRU tiebreak ------
    cblk = jnp.concatenate([mblk1u, m2eff], axis=0)     # (2A, cols)
    meta_c = cblk[:, MT_META]
    est = _estimate_block(spec, counters, dk,
                          cblk[:, 3:3 + rows],
                          cblk[:, 3 + rows:3 + rows + dkp])
    # aliased choice sets: the second half duplicates the first — mask it
    # out of the victim scan so the insert lands once
    half2 = jnp.arange(2 * A, dtype=jnp.int32) >= A
    pad = (meta_c == _I32_MAX) | (same_km & half2)
    okey1 = jnp.where(pad, _I32_MAX, jnp.where(meta_c < 0, -1, est))
    mmin = jnp.min(okey1)
    okey2 = jnp.where(okey1 == mmin, meta_c, _I32_MAX)  # LRU among freq ties
    tslot = jnp.argmin(okey2)
    miss = ~hit
    do_ins = miss & (okey1[tslot] != _I32_MAX)          # always admit
    candrow = _main_record(_with_meta(krec, mst))
    in1 = do_ins & (tslot < A)
    in2 = do_ins & (tslot >= A)
    j1 = jnp.minimum(tslot, A - 1)
    j2 = jnp.clip(tslot - A, 0, A - 1)
    mb1f = _lset_row(mblk1u, j1, candrow, in1)
    mb2f = _lset_row(m2eff, j2, candrow, in2)
    mb2f = jnp.where(same_km, mb1f, mb2f)

    zm = _sched_dep(mb2f)
    mtab = _ldus_block(mtab, mb1f | zm, km1)
    mtab = _ldus_block(mtab, mb2f, km2)

    counted = (hit & (t >= params[P_WARMUP])).astype(jnp.int32)
    regs = jnp.stack([size, regs[R_PCOUNT], t + 1, regs[R_HITS] + counted,
                      regs[4], regs[5], regs[6], regs[7]])
    new_state = {**state, "counters": counters, "doorkeeper": dk,
                 "mtab": mtab, "regs": regs}
    return new_state, hit.astype(jnp.int32)


def _one_access(spec: StepSpec, params: jnp.ndarray, state: dict,
                klo, khi, kidx, kdkb, kwset, kmset, krec):
    """Advance the cache state by one access; returns (state, hit).

    Dispatch is static (Python, at trace time): ``spec.assoc is None``
    takes the flat exact path, otherwise ``spec.policy`` selects which
    admission/victim rules run on the set-associative machinery.
    ``policy="wtinylfu"`` lowers the same program as the default spec, and
    the R7 digests in ``analysis/fingerprints.json`` pin that program
    (tests/test_policy_panel.py).  The set path takes the access's packed
    record ``krec`` (:func:`access_records`); the flat path has no records
    and ignores it.
    """
    if spec.assoc is None:
        return _one_access_flat(spec, params, state, klo, khi, kidx, kdkb)
    set_access = {"s3fifo": _one_access_set_s3fifo,
                  "arc": _one_access_set_arc,
                  "lfu": _one_access_set_lfu}.get(spec.policy,
                                                  _one_access_set)
    return set_access(spec, params, state, klo, khi, kidx, kdkb, kwset,
                      kmset, krec)


# ---------------------------------------------------------------------------
# epoch-boundary rebalance: move the runtime window/main boundary
# ---------------------------------------------------------------------------

def _rebalance_flat(spec: StepSpec, params, state, nq):
    regs = state["regs"]
    wlo, whi, wmeta = state["wlo"], state["whi"], state["wmeta"]
    mlo, mhi, mmeta = state["mlo"], state["mhi"], state["mmeta"]
    wcount, mcount = regs[R_WCOUNT], regs[R_MCOUNT]
    pcount = regs[R_PCOUNT]
    total = params[P_WINDOW_CAP] + params[P_MAIN_CAP]
    mcap_new = total - nq

    # -- window shrink: evict the LRU residents beyond the new quota ---------
    res_w = (wmeta >= 0) & (wmeta < _I32_MAX)
    n_wev = jnp.maximum(0, wcount - nq)
    ranks = jnp.argsort(jnp.argsort(jnp.where(res_w, wmeta, _I32_MAX)))
    evict = res_w & (ranks < n_wev)
    # ... migrating the strongest (most recent) of them into main's free
    # room, probation, original stamp (stamps are globally unique so SLRU
    # order is preserved); the weakest beyond the room are dropped
    room = jnp.maximum(0, mcap_new - mcount)
    dranks = jnp.argsort(jnp.argsort(jnp.where(evict, -wmeta, _I32_MAX)))
    mig = evict & (dranks < room)
    free_order = jnp.argsort(mmeta != _EMPTY)    # stable: empty slots first
    tgt = jnp.where(mig, free_order[dranks], spec.main_slots)  # OOB -> drop
    mlo = mlo.at[tgt].set(wlo, mode="drop")
    mhi = mhi.at[tgt].set(whi, mode="drop")
    mmeta = mmeta.at[tgt].set(wmeta, mode="drop")
    midx = state["midx"].at[tgt].set(state["widx"], mode="drop")
    mdkb = state["mdkb"].at[tgt].set(state["wdkb"], mode="drop")
    wlo = jnp.where(evict, -1, wlo)
    whi = jnp.where(evict, -1, whi)
    wmeta = jnp.where(evict, _EMPTY, wmeta)
    wcount = wcount - n_wev
    mcount = mcount + mig.sum()

    # -- window grow: evict main's weakest beyond the shrunken budget --------
    # (mutually exclusive with the migration above: only one side shrinks)
    res_m = (mmeta >= 0) & (mmeta < _I32_MAX)
    n_mev = jnp.maximum(0, mcount - mcap_new)
    ranks_m = jnp.argsort(jnp.argsort(jnp.where(res_m, mmeta, _I32_MAX)))
    evict_m = res_m & (ranks_m < n_mev)
    pcount = pcount - (evict_m & (mmeta >= _PROT)).sum()
    mlo = jnp.where(evict_m, -1, mlo)
    mhi = jnp.where(evict_m, -1, mhi)
    mmeta = jnp.where(evict_m, _EMPTY, mmeta)
    mcount = mcount - n_mev

    regs = jnp.stack([regs[R_SIZE], pcount, regs[R_T], regs[R_HITS],
                      nq, wcount, mcount, jnp.int32(0)])
    return {**state, "wlo": wlo, "whi": whi, "wmeta": wmeta,
            "midx": midx, "mdkb": mdkb,
            "mlo": mlo, "mhi": mhi, "mmeta": mmeta, "regs": regs}


def _rebalance_set(spec: StepSpec, params, state, nq):
    A = spec.assoc
    regs = state["regs"]
    wtab, mtab = state["wtab"], state["mtab"]
    total = params[P_WINDOW_CAP] + params[P_MAIN_CAP]
    mcap_new = total - nq
    nws, nms = spec.window_sets, spec.main_sets
    way = jnp.arange(A, dtype=jnp.int32)

    def compact(tab, n_sets, ncols, meta_col, usable):
        """Per-set: sort records strongest-first, keep the first ``usable``,
        blank the rest; returns (new tab3d, sorted tab3d, evicted mask)."""
        t3 = tab.reshape(n_sets, A, ncols)
        meta = t3[:, :, meta_col]
        order = jnp.argsort(-meta, axis=1)       # residents first, empty last
        t3s = jnp.take_along_axis(t3, order[:, :, None], axis=1)
        keep = way[None, :] < usable[:, None]
        metas = t3s[:, :, meta_col]
        evict = (metas >= 0) & (metas < _I32_MAX) & ~keep
        blank = jnp.zeros((ncols,), jnp.int32).at[0].set(-1).at[1].set(-1) \
            .at[meta_col].set(_EMPTY)
        t3n = jnp.where(keep[:, :, None], t3s, blank[None, None, :])
        return t3n, t3s, evict

    # window quota distribution (jnp twin of core.adaptive.window_set_ways):
    # uniform while nq >= nws (bit-identical to the static set_ways padding,
    # preserving pinned-quota == static); below nws the ways go to the nq
    # most-loaded sets of the finished epoch (state["wsl"] telemetry) so a
    # small quota cannot starve hot sets under skewed key->set load.  The
    # argsort is stable, so ties break by set index like the host rule.
    load = state["wsl"]
    uniform = nq // nws + (jnp.arange(nws, dtype=jnp.int32) < nq % nws)
    order = jnp.argsort(-load)                   # hottest first, stable
    ranks = jnp.zeros((nws,), jnp.int32).at[order].set(
        jnp.arange(nws, dtype=jnp.int32))
    uw = jnp.where(nq < nws, (ranks < nq).astype(jnp.int32), uniform)
    um = mcap_new // nms + (jnp.arange(nms, dtype=jnp.int32) < mcap_new % nms)
    w3n, w3s, w_evict = compact(wtab, nws, spec.wcols, WT_META, uw)
    m3n, _, _ = compact(mtab, nms, spec.mcols, MT_META, um)
    wtab = w3n.reshape(-1, spec.wcols)
    mtab = m3n.reshape(-1, spec.mcols)

    # -- migrate displaced window records into a free usable way of their
    # stored first-choice main set (sequential: targets collide; the traced
    # trip count is the number of evictions, ~delta per epoch)
    ev_flat = w_evict.reshape(-1)
    recs = w3s.reshape(-1, spec.wcols)
    ev_order = jnp.argsort(~ev_flat)             # stable: evicted first

    def body(i, mtab_c):
        rec = recs[ev_order[i]]
        s = rec[WT_MSET]
        blk = jax.lax.dynamic_slice(mtab_c, (s * A, 0), (A, spec.mcols))
        meta = blk[:, MT_META]
        u = mcap_new // nms + (s < mcap_new % nms).astype(jnp.int32)
        free = (meta == _EMPTY) & (way < u)
        j = jnp.argmax(free)
        mainrow = _main_record(rec)
        row = jnp.where(free.any(), mainrow, blk[j])
        return jax.lax.dynamic_update_slice(
            mtab_c, blk.at[j].set(row), (s * A, 0))

    mtab = jax.lax.fori_loop(0, ev_flat.sum(), body, mtab)

    regs = jnp.stack([regs[R_SIZE], regs[R_PCOUNT], regs[R_T], regs[R_HITS],
                      nq, regs[R_WCOUNT], regs[R_MCOUNT], jnp.int32(0)])
    return {**state, "wtab": wtab, "mtab": mtab, "regs": regs,
            "wsl": jnp.zeros_like(load), "wuw": uw}


def rebalance(spec: StepSpec, params: jnp.ndarray, state: dict,
              new_quota) -> dict:
    """Move the runtime window/main boundary to ``new_quota`` (adaptive mode).

    Runs between epochs inside the compiled program (no host sync): clamps
    the quota to the geometry, evicts/compacts each table down to its new
    budget, migrates displaced window records into main's free room
    (probation, stamps preserved), and resets the per-epoch telemetry
    register ``R_EHITS``.  O(slots·log) once per epoch — amortized over the
    epoch it leaves the per-access cost untouched.  A rebalance to the
    current quota only compacts (hit-sequence no-op), which is what makes
    the pinned-quota differential tests possible.
    """
    assert spec.adaptive, "rebalance requires StepSpec.adaptive"
    total = params[P_WINDOW_CAP] + params[P_MAIN_CAP]
    nq = jnp.clip(jnp.asarray(new_quota, jnp.int32),
                  jnp.maximum(1, total - spec.main_slots),
                  jnp.minimum(spec.window_slots, total - 1))
    if spec.assoc is None:
        return _rebalance_flat(spec, params, state, nq)
    return _rebalance_set(spec, params, state, nq)


# ---------------------------------------------------------------------------
# reference backend: lax.scan over the chunk (jit twin of the fused kernel)
# ---------------------------------------------------------------------------

def _step_lanes(fn, spec: StepSpec, params, state, lo, hi, n_valid,
                lane_trace: bool = True, **kw):
    """Dispatch a ``streams=B`` step: vmap the ``streams=1`` program.

    ``params`` may be shared ``(NPARAMS,)`` or per-lane ``(B, NPARAMS)``
    (vmapped sweeps); all state leaves and key lanes carry a leading lane
    axis.  ``n_valid`` may be shared (scalar) or per-lane ``(B,)``.  While
    the vmapped trace runs, :data:`_LANE_TRACE` re-expresses every
    per-lane-indexed single-slot write as a fused masked select (see the
    flag's comment) — the pallas path skips the flag (``lane_trace=False``):
    pallas' own vmap rule batches the kernel by a grid dimension, inside
    which the indices stay unbatched.
    """
    B = spec.streams
    if lo.ndim != 2 or lo.shape[0] != B:
        raise ValueError(
            f"streams={B} expects (B, T) key lanes; got trace shape "
            f"{tuple(lo.shape)} — one row per tenant lane")
    lspec = replace(spec, streams=1)
    axes = [0 if params.ndim == 2 else None, 0, 0, 0]
    args = [params, state, lo, hi]
    if n_valid is not None:
        nv = jnp.asarray(n_valid, jnp.int32)
        axes.append(0 if nv.ndim else None)
        args.append(nv)

        def run(p, s, l, h, n):
            return fn(lspec, p, s, l, h, n, **kw)
    else:
        def run(p, s, l, h):
            return fn(lspec, p, s, l, h, **kw)
    prev = _LANE_TRACE[0]
    _LANE_TRACE[0] = lane_trace
    try:
        return jax.vmap(run, in_axes=tuple(axes))(*args)
    finally:
        _LANE_TRACE[0] = prev


def step_ref(spec: StepSpec, params: jnp.ndarray, state: dict,
             lo: jnp.ndarray, hi: jnp.ndarray,
             n_valid: jnp.ndarray | int | None = None,
             *, unroll: int | None = None):
    """Sequentially simulate ``lo/hi`` accesses; returns (state, hit_flags).

    ``n_valid`` masks padded tails: accesses at positions >= n_valid leave the
    state untouched and report hit=0.  Bit-for-bit identical to step_pallas.

    ``unroll=None`` picks per layout: 4 for the flat path (hides scalar
    latency between its big reductions), 1 for the set path (unrolling
    defeats XLA CPU's in-place buffer reuse across the chained single-word
    updates, reintroducing O(state) copies per access).

    ``spec.streams = B > 1`` expects ``(B, T)`` key lanes and lane-axis
    state and runs all B tenant lanes in one vmapped scan (unroll forced to
    1: the lane axis already fills the vector units).
    """
    if spec.streams > 1:
        return _step_lanes(step_ref, spec, params, state, lo, hi, n_valid,
                           unroll=1 if unroll is None else unroll)
    if unroll is None:
        unroll = 4 if spec.assoc is None else 1
    (b,) = lo.shape
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    with jax.named_scope("probes"):
        kidx, kdkb, kwset, kmset = precompute_probes(spec, lo, hi)
    # the indices the body uses as scalars scan as 1-D columns: a (T, k)
    # input makes the TPU relayout the whole array on every access to
    # slice one row and split it into scalars
    xs = (lo, hi, tuple(kidx.T), tuple(kdkb.T), kwset, tuple(kmset.T))
    if n_valid is not None:
        xs += (jnp.arange(b, dtype=jnp.int32),)
    with jax.named_scope("layout"):
        state = _scan_tables(spec, state, True)
    # a set-path access reads its window record as one row of its block's
    # packed records; under lanes (_LANE_TRACE) the body builds it from the
    # (B,) lane vectors, which reads 4% faster there on a v5e; the flat
    # path has none
    packed = spec.assoc is not None and not _LANE_TRACE[0]

    def access(carry, klo, khi, ki, kd, kw, km, krec):
        ki, kd, km = jnp.stack(ki), jnp.stack(kd), jnp.stack(km)
        if krec is None and spec.assoc is not None:
            krec = access_records(klo, khi, ki, kd, km)
        return _one_access(spec, params, carry, klo, khi, ki, kd, kw, km,
                           krec)

    if n_valid is None:
        # fast path: no tail masking, no per-step state merge
        def body(carry, x):
            return access(carry, *x)
    else:
        n_valid = jnp.asarray(n_valid, jnp.int32)

        def body(carry, x):
            *x, i, krec = x
            new, hit = access(carry, *x, krec)
            active = i < n_valid
            merged = jax.tree_util.tree_map(
                lambda n, o: jnp.where(active, n, o), new, carry)
            return merged, jnp.where(active, hit, 0)

    def scan(carry, x):
        # the records are built once a block, not once a replay, so outside
        # the once-a-replay `probes` scope
        recs = None
        if packed:
            recs = access_records(x[0], x[1], jnp.stack(x[2], -1),
                                  jnp.stack(x[3], -1), jnp.stack(x[5], -1))
        return jax.lax.scan(body, carry, x + (recs,), unroll=unroll)

    if not packed or b <= _RECORD_BLOCK:
        state, hits = scan(state, xs)
    else:
        state, hits = _scan_blocks(scan, state, xs, b)
    with jax.named_scope("layout"):
        return _scan_tables(spec, state, False), hits


def _scan_blocks(scan, state, xs, n: int):
    """``scan`` over ``n`` accesses a record block at a time: a loop over
    whole :data:`_RECORD_BLOCK` blocks, then one call for the tail.  Blocks
    are sliced from the columns and their hit flags written into one
    ``(n,)`` buffer in place: a ``(blocks, K)`` reshape would relayout each
    T-long column on the TPU.  Returns the state and the hit flags."""
    K = _RECORD_BLOCK
    nb, tail = divmod(n, K)

    def block(j, carry):
        state, hits = carry
        x = jax.tree.map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, j * K, K), xs)
        state, h = scan(state, x)
        return state, jax.lax.dynamic_update_slice_in_dim(hits, h, j * K, 0)

    state, hits = jax.lax.fori_loop(
        0, nb, block, (state, jnp.zeros((n,), jnp.int32)))
    if tail:
        state, h = scan(state, jax.tree.map(lambda c: c[nb * K:], xs))
        hits = jax.lax.dynamic_update_slice_in_dim(hits, h, nb * K, 0)
    return state, hits


# ---------------------------------------------------------------------------
# fused Pallas kernel: whole chunk, state pinned in VMEM, buffers donated
# ---------------------------------------------------------------------------

# number of streamed (non-state) VMEM inputs: lo, hi, kidx, kdkb, kwset,
# kmset, recs (the packed records of access_records)
_N_STREAM = 7


def _step_kernel(spec: StepSpec, lo_ref, hi_ref, kidx_ref, kdkb_ref,
                 kwset_ref, kmset_ref, recs_ref, scal_ref, *refs):
    keys = _state_keys(spec)
    n_state = len(keys)
    in_refs = refs[:n_state]
    out_refs = refs[n_state:2 * n_state]
    hits_ref = refs[2 * n_state]

    params = jnp.stack([scal_ref[i] for i in range(NPARAMS)])
    n_valid = scal_ref[NPARAMS]
    lo = lo_ref[...]
    hi = hi_ref[...]
    kidx = kidx_ref[...]
    kdkb = kdkb_ref[...]
    kwset = kwset_ref[...]
    kmset = kmset_ref[...]
    recs = recs_ref[...]
    state0 = _scan_tables(spec, {k: r[...] for k, r in zip(keys, in_refs)},
                          True)
    hits0 = jnp.zeros(lo.shape, jnp.int32)

    def body(i, carry):
        state, hits = carry
        new, hit = _one_access(spec, params, state, lo[i], hi[i],
                               kidx[i], kdkb[i], kwset[i], kmset[i], recs[i])
        return new, hits.at[i].set(hit)

    state, hits = jax.lax.fori_loop(0, n_valid, body, (state0, hits0))
    state = _scan_tables(spec, state, False)
    for k, r in zip(keys, out_refs):
        r[...] = state[k]
    hits_ref[...] = hits


def step_pallas(spec: StepSpec, params: jnp.ndarray, state: dict,
                lo: jnp.ndarray, hi: jnp.ndarray,
                n_valid: jnp.ndarray | int | None = None,
                *, interpret: bool = True):
    """Fused chunk step: one launch, state VMEM-resident and donated.

    Same signature/semantics as :func:`step_ref`.  Probes and set indices are
    precomputed vectorized outside the kernel (they are pure functions of the
    keys) and streamed in with the key lanes.  ``spec.streams > 1`` batches
    through pallas' vmap rule (a fresh grid dimension; the kernel body stays
    unbatched, so the lane-write discipline is not needed).
    """
    if spec.streams > 1:
        return _step_lanes(step_pallas, spec, params, state, lo, hi,
                           n_valid, lane_trace=False, interpret=interpret)
    (b,) = lo.shape
    n_valid = b if n_valid is None else n_valid
    lo = lo.astype(jnp.int32)
    hi = hi.astype(jnp.int32)
    kidx, kdkb, kwset, kmset = precompute_probes(spec, lo, hi)
    recs = access_records(lo, hi, kidx, kdkb, kmset)
    scal = jnp.concatenate([
        params.astype(jnp.int32),
        jnp.asarray(n_valid, jnp.int32).reshape(1)])
    kernel = functools.partial(_step_kernel, spec)
    keys = _state_keys(spec)
    n_state = len(keys)
    state_vals = [state[k] for k in keys]
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(
            [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in state_vals]
            + [jax.ShapeDtypeStruct((b,), jnp.int32)]),
        in_specs=(
            [pl.BlockSpec(memory_space=pltpu.VMEM)] * _N_STREAM
            + [pl.BlockSpec(memory_space=pltpu.SMEM)]     # packed scalars
            + [pl.BlockSpec(memory_space=pltpu.VMEM)] * n_state),
        out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)]
                        * (n_state + 1)),
        # donate every state buffer: input i+_N_STREAM+1 -> output i
        input_output_aliases={i + _N_STREAM + 1: i for i in range(n_state)},
        interpret=interpret,
    )(lo, hi, kidx, kdkb, kwset, kmset, recs, scal, *state_vals)
    new_state = dict(zip(keys, outs[:n_state]))
    return new_state, outs[n_state]
