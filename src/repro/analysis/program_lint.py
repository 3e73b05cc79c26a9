"""Static lint of lowered engine programs: the in-place discipline as rules.

Every throughput claim in this repo rests on *compiled-program* properties
— the XLA-CPU in-place discipline of ``docs/ARCHITECTURE.md`` — that the
benchmark gates only catch after the fact, noisily, days late.  This
module enforces them at lowering time: it parses ``compiled.as_text()``
with the call-graph / trip-count machinery of :mod:`repro.analysis.hlo_cost`
and checks structural rules over the access-scan bodies.

Rules (each cross-referenced to the ARCHITECTURE.md symptom table):

``R1``  no ``scatter`` op reachable from an access-scan body.  Symptom:
        per-access fixed ~µs dispatch; the lane-batching regression class
        (scatter-free lane writes are the whole point of ``streams``).
``R2``  per-access write footprint bounded: every ``dynamic-update-slice``
        in the scan body updates O(ways) words, never a table-shaped
        region.  Symptom: flatness collapse proportional to capacity.
``R3``  no table-shaped ``copy`` / non-DUS fusion output in the scan body
        (the chain-split-allocation cliff: a full-buffer materialization
        per access).  Symptom: flatness collapse + overhead ~1 —
        "full-buffer copy (aliasing broke)".
``R4``  no ``outer_dimension_partitions`` thread dispatch on sub-512B
        outputs.  Symptom: flatness collapse + big overhead at one width
        tier — "partitioned body fusion".
``R5``  donation honored: state buffers input/output-aliased, zero
        table-shaped entry-level copies.  Symptom: same as R3, at the
        program boundary instead of inside the scan.
``R6``  collective cadence: zero collectives reachable from any while
        body for ``mesh_exchange="chunk"`` (entry/exit all-gather only),
        none reachable from the access body for ``"stale"`` (per-epoch
        fold only), none at all in single-device programs.  This is the
        62.8x per-access-psum bug of PR 6, expressed statically.
``R7``  byte-identity fingerprints: every "compiles the identical
        program" contract (``policy`` default, ``streams=1``,
        ``shards=1``, ``adaptive=False``, ``integrity=False``,
        ``events=False``) lowers
        byte-identical text, and its digest matches the committed
        registry (``fingerprints.json``, keyed by jax version + backend;
        refresh with ``tools/lint_programs.py --update``).
``R0``  structural sanity: the access scan itself must exist as a
        known-trip-count while (catches a restructure that would silently
        void R1-R3/R6's scoping).

The text analysis (:func:`lint_hlo`) is pure — no jax import — so fixture
HLO and committed repro text lint without lowering anything.  The config
matrix (:func:`default_matrix` / :func:`run_matrix`) lowers the real
engine across flat/assoc x static/adaptive x shards x streams x policy x
mesh chunk/stale; ``tools/lint_programs.py`` is the CLI and CI step.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable

from .hlo_cost import (_COLLECTIVES, _TRIP_COUNT, _nbytes, _nelems,
                       _split_computations, _trip_count)

# ---------------------------------------------------------------------------
# rule table (ids -> one-line contract; rendered by --list-rules and docs)
# ---------------------------------------------------------------------------

RULES = {
    "R0": "access scan exists as a known-trip-count while loop",
    "R1": "no scatter op reachable from an access-scan body",
    "R2": "every DUS in the scan body updates O(ways) words, "
          "never a table-shaped region",
    "R3": "no table-shaped copy / non-DUS fusion output in the scan body "
          "(chain-split allocation cliff)",
    "R4": "no outer_dimension_partitions thread dispatch on sub-512B "
          "outputs",
    "R5": "donation honored: state buffers input/output-aliased, no "
          "table-shaped entry copies",
    "R6": "collective cadence: chunk = entry/exit only, stale = "
          "per-epoch only, single-device = none",
    "R7": "byte-identity fingerprints match the committed registry",
}

# default scan lengths for the lowered matrix — deliberately NOT powers of
# two so trip counts cannot collide with internal geometry loops (set
# counts, ways, rebalance fori bounds are all powers of two)
T_STEP = 96          # plain step programs: accesses per chunk
E_EPOCH = 192        # runner programs: accesses per merge/climb epoch
NE_EPOCHS = 2        # epochs per lowered runner program
T_TAIL = 23          # mesh programs: tail accesses outside the epoch scan
B_LANES = 4          # lane-batched entries


@dataclass(frozen=True)
class LintBounds:
    """Per-program parameters the rules check against.

    ``access_trips`` identifies the access-scan while loops by their
    known trip counts — the linter controls the lowering, so it knows the
    chunk lengths it lowered with.  ``max_update_elems`` is the R2 bound
    (None disables R2 — flat programs write O(capacity) by design).
    ``table_elems_floor`` is the smallest output (elements) R3/R5 call
    "table-shaped".  ``expect_aliases`` arms R5 with the number of state
    leaves that must be input/output-aliased.  ``mesh_exchange`` selects
    the R6 cadence contract (None = single-device, zero collectives).
    """
    access_trips: tuple = ()
    assoc: bool = False
    streams: int = 1
    max_update_elems: int | None = None
    table_elems_floor: int = 1024
    mesh_exchange: str | None = None
    expect_aliases: int | None = None
    partition_floor_bytes: int = 512


@dataclass
class Violation:
    rule: str
    config: str
    where: str
    message: str

    def __str__(self):
        return f"[{self.rule}] {self.config}: {self.message} ({self.where})"

    def to_dict(self):
        return {"rule": self.rule, "config": self.config,
                "where": self.where, "message": self.message}


# ---------------------------------------------------------------------------
# call-graph helpers over _split_computations output
# ---------------------------------------------------------------------------

def _reachable(comps, roots):
    """Names of computations reachable from ``roots`` through any call
    edge (while cond/body, fusion calls, call, conditional branches)."""
    seen: set[str] = set()
    stack = [r for r in roots if r]
    while stack:
        name = stack.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for op in comps[name].ops.values():
            stack.extend(c for c in op.called if c not in seen)
    return seen


def _find_whiles(comps):
    """All while ops: (comp_name, op, trips_or_None, body_name)."""
    out = []
    for cn, comp in comps.items():
        for op in comp.ops.values():
            if op.kind != "while":
                continue
            called = [c for c in op.called if c in comps]
            cond = called[0] if called else None       # condition=, body=
            body = called[1] if len(called) > 1 else None
            tm = _TRIP_COUNT.search(op.line)
            trips = int(tm.group(1)) if tm else None
            if trips is None and cond:
                t = _trip_count(comps[cond])
                trips = int(t) if t is not None else None
            out.append((cn, op, trips, body))
    return out


def _max_out_elems(op) -> int:
    """Largest tuple element of the op's output, in elements."""
    if not op.out_shapes:
        return 0
    return int(max(_nelems([s]) for s in op.out_shapes))


def _is_collective(kind: str) -> bool:
    return any(kind.startswith(c) for c in _COLLECTIVES) \
        and not kind.endswith("-done")


# ---------------------------------------------------------------------------
# the linter core: pure text analysis
# ---------------------------------------------------------------------------

def lint_hlo(text: str, bounds: LintBounds, config: str = "") -> list:
    """Lint one compiled module's text against ``bounds``.  Pure — usable
    on committed fixture HLO as well as live lowerings."""
    comps, entry = _split_computations(text)
    out: list[Violation] = []
    whiles = _find_whiles(comps)

    # XLA may unroll the scan body (flat programs unroll 4x): a while
    # with trips = T/k for a small integer k is still the access loop
    def _is_access(t):
        return any(t == at or (t and at % t == 0 and 2 <= at // t <= 8)
                   for at in bounds.access_trips)

    access_bodies = [b for _, _, t, b in whiles
                     if b and t is not None and _is_access(t)]
    if bounds.access_trips and not access_bodies:
        out.append(Violation(
            "R0", config, entry or "?",
            f"no while loop with trip count in {bounds.access_trips} — "
            "the access scan is gone or restructured; rule scoping is "
            "void"))
    access_reach = _reachable(comps, access_bodies)
    while_reach = _reachable(comps, [b for _, _, _, b in whiles if b])

    # R1: no scatter reachable from the access scan.  XLA-CPU's scatter
    # expander rewrites every scatter into a sequential while loop with a
    # KNOWN trip count (= number of scatter indices) before the final
    # HLO, so the compiled-text signature is either a literal scatter op
    # (other backends) or a known-trip inner while nested in the access
    # body — healthy inner loops there (the §3.3 reset, the ghost
    # saturation clear) all have where-gated DYNAMIC trip counts.
    access_body_names = set(access_bodies)
    for cn in sorted(access_reach):
        for op in comps[cn].ops.values():
            if op.kind == "scatter":
                out.append(Violation(
                    "R1", config, f"{cn}/{op.name}",
                    "scatter op in the access-scan body — per-access "
                    "dispatch overhead (lane writes must be fused "
                    "one-hot selects, table writes single-word DUS)"))
    for cn, op, trips, body in whiles:
        if cn in access_reach and body not in access_body_names \
                and trips is not None and not _is_access(trips):
            out.append(Violation(
                "R1", config, f"{cn}/{op.name}",
                f"known-trip-count ({trips}) while nested in the "
                "access-scan body — the expanded-scatter signature "
                "(a serialized per-index write loop per access)"))

    # R2: DUS write footprint inside the access scan
    if bounds.max_update_elems is not None:
        for cn in sorted(access_reach):
            comp = comps[cn]
            for op in comp.ops.values():
                if op.kind != "dynamic-update-slice" or \
                        len(op.operands) < 2:
                    continue
                upd = comp.ops.get(op.operands[1])
                if upd is None:
                    continue
                elems = _max_out_elems(upd)
                if elems > bounds.max_update_elems:
                    out.append(Violation(
                        "R2", config, f"{cn}/{op.name}",
                        f"DUS updates {elems} elements per access "
                        f"(bound {bounds.max_update_elems} = O(ways)) — "
                        "a table-shaped write region sinks flatness"))

    # R3: table-shaped copy / non-DUS fusion output in the access scan.
    # Lane programs (streams>1) legitimately materialize full-array
    # one-hot-select fusions; flat programs are O(capacity) by design.
    if bounds.assoc and bounds.streams == 1:
        for cn in sorted(access_reach):
            comp = comps[cn]
            for op in comp.ops.values():
                big = _max_out_elems(op) >= bounds.table_elems_floor
                if not big:
                    continue
                if op.kind == "copy":
                    out.append(Violation(
                        "R3", config, f"{cn}/{op.name}",
                        f"table-shaped copy ({_max_out_elems(op)} elems) "
                        "in the access-scan body — the chain-split "
                        "allocation cliff (aliasing broke)"))
                elif op.kind == "fusion":
                    fused = [comps[c] for c in op.called if c in comps]
                    has_dus = any(
                        o.kind == "dynamic-update-slice"
                        for f in fused for o in f.ops.values())
                    if not has_dus:
                        out.append(Violation(
                            "R3", config, f"{cn}/{op.name}",
                            f"table-shaped fusion output "
                            f"({_max_out_elems(op)} elems) with no DUS "
                            "root in the access-scan body — a "
                            "full-buffer materialization per access"))

    # R4: partitioned thread dispatch on tiny outputs (whole module)
    for cn in sorted(comps):
        for op in comps[cn].ops.values():
            if "outer_dimension_partitions" not in op.line:
                continue
            nb = _nbytes(op.out_shapes)
            if nb < bounds.partition_floor_bytes:
                out.append(Violation(
                    "R4", config, f"{cn}/{op.name}",
                    f"outer_dimension_partitions on a {int(nb)}B output "
                    f"(< {bounds.partition_floor_bytes}B) — thread "
                    "dispatch costs more than the work it splits"))

    # R5: donation honored at the program boundary
    if bounds.expect_aliases is not None:
        header = text.splitlines()[0] if text else ""
        n_alias = header.count("may-alias") + header.count("must-alias")
        if n_alias < bounds.expect_aliases:
            out.append(Violation(
                "R5", config, "entry",
                f"only {n_alias} of {bounds.expect_aliases} state "
                "buffers input/output-aliased — donation is not "
                "reaching the compiled program"))
        if entry and entry in comps:
            for op in comps[entry].ops.values():
                if op.kind == "copy" and \
                        _max_out_elems(op) >= bounds.table_elems_floor:
                    out.append(Violation(
                        "R5", config, f"{entry}/{op.name}",
                        f"table-shaped entry-level copy "
                        f"({_max_out_elems(op)} elems) — a donated "
                        "buffer is being duplicated at the boundary"))

    # R6: collective cadence
    coll = [(cn, op) for cn in comps for op in comps[cn].ops.values()
            if _is_collective(op.kind)]
    if bounds.mesh_exchange is None:
        for cn, op in coll:
            out.append(Violation(
                "R6", config, f"{cn}/{op.name}",
                f"{op.kind} in a single-device program"))
    elif bounds.mesh_exchange == "chunk":
        for cn, op in coll:
            if cn in while_reach:
                out.append(Violation(
                    "R6", config, f"{cn}/{op.name}",
                    f"{op.kind} inside a loop body — chunk mode pays "
                    "its collectives at program entry/exit only (the "
                    "62.8x per-access-psum bug class)"))
    else:                                   # "stale": per-epoch fold only
        for cn, op in coll:
            if cn in access_reach:
                out.append(Violation(
                    "R6", config, f"{cn}/{op.name}",
                    f"{op.kind} inside the access-scan body — stale "
                    "mode's one collective is the per-epoch "
                    "merge_halve_mesh fold"))
    return out


# ---------------------------------------------------------------------------
# R7: byte-identity fingerprint registry
# ---------------------------------------------------------------------------

REGISTRY_PATH = Path(__file__).with_name("fingerprints.json")

# the canonical pin geometry — shared by the historic per-test pins this
# registry replaced (tests/test_sketch_step.py, test_policy_panel.py,
# test_streams.py all lowered this same spec family)
_FP_BASE = dict(width=256, rows=4, dk_bits=1024, window_slots=8,
                main_slots=64, assoc=8)

# contract name -> StepSpec override that must compile the byte-identical
# program to the base spec (the override merely spells out a default)
FINGERPRINT_CONTRACTS = {
    "shards1": {"shards": 1},
    "policy-default": {"policy": "wtinylfu"},
    "streams1": {"streams": 1},
    "adaptive-off": {"adaptive": False},
    "integrity-off": {"integrity": False},
    "events-off": {"events": False},
}


def env_key() -> str:
    """HLO text varies across jax versions/backends; digests are only
    comparable within one environment."""
    import jax
    return f"jax-{jax.__version__}-{jax.default_backend()}"


def pin_program_text(**overrides) -> str:
    """Lower the canonical pin program (unoptimized module text).

    Lowers from a cleared trace/lowering cache: jax's auto-numbered
    private helpers (``_where_N``, ``floor_divide_N``...) pick up
    process-history-dependent suffixes — and occasionally an extra
    deduplication-miss copy — when the global lowering caches are warm
    from unrelated programs (e.g. mid-test-suite), which would make the
    R7 digest compare process-order-dependent.  A cold cache lowers the
    byte-identical text every time, in any process.
    """
    import jax
    import numpy as np
    from repro.kernels.sketch_common import keys_to_lanes
    from repro.kernels.sketch_step import (StepSpec, init_step_state,
                                           make_step_params, step_ref)
    jax.clear_caches()
    spec = StepSpec(**{**_FP_BASE, **overrides})
    params = make_step_params(4, 48, 38, 700, 7, 0)
    lo, hi = keys_to_lanes(np.arange(16, dtype=np.uint64))
    return jax.jit(step_ref, static_argnums=0).lower(
        spec, params, init_step_state(spec), lo, hi).as_text()


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def load_registry(path: Path = REGISTRY_PATH) -> dict:
    if Path(path).exists():
        return json.loads(Path(path).read_text())
    return {}


def check_fingerprints(update: bool = False,
                       registry_path: Path = REGISTRY_PATH,
                       contracts: dict | None = None):
    """Verify every identical-program contract; returns
    ``(violations, notes)``.  Pair equality (base text == variant text)
    is always enforced; the committed digest is only compared when the
    registry has an entry for this environment (``--update`` writes one).
    """
    contracts = FINGERPRINT_CONTRACTS if contracts is None else contracts
    violations: list[Violation] = []
    notes: list[str] = []
    base = pin_program_text()
    key = env_key()
    reg = load_registry(registry_path)
    env = reg.get(key, {})
    digests = {"base": _digest(base)}
    for name, ov in contracts.items():
        var = pin_program_text(**ov)
        digests[name] = _digest(var)
        if var != base:
            violations.append(Violation(
                "R7", name, "lowering",
                f"spelling out the default ({ov}) lowers a DIFFERENT "
                "program — an identical-program contract broke"))
    if update:
        reg[key] = digests
        Path(registry_path).write_text(
            json.dumps(reg, indent=2, sort_keys=True) + "\n")
        notes.append(f"registry updated for {key} "
                     f"({len(digests)} digests)")
        return violations, notes
    if not env:
        notes.append(f"no registry entry for {key} — digest check "
                     "skipped (pair equality still enforced); run "
                     "tools/lint_programs.py --update to pin this "
                     "environment")
        return violations, notes
    for name, dg in digests.items():
        want = env.get(name)
        if want is None:
            notes.append(f"contract {name!r} not in registry for {key}")
        elif want != dg:
            violations.append(Violation(
                "R7", name, key,
                "lowered-program digest drifted from the committed "
                "registry — if the lowering change is intentional, "
                "refresh with tools/lint_programs.py --update"))
    return violations, notes


def assert_identical_program(name: str):
    """Test-facing one-liner for the identical-program pins: lowers the
    base and the ``name`` contract's variant, asserts byte-identity, and
    (when this environment is pinned) the committed digest."""
    ov = FINGERPRINT_CONTRACTS[name]
    base = pin_program_text()
    var = pin_program_text(**ov)
    assert var == base, (
        f"contract {name!r}: spelling out the default {ov} lowered a "
        "different program")
    env = load_registry().get(env_key(), {})
    if env:
        assert _digest(var) == env[name], (
            f"contract {name!r}: program digest drifted from the "
            "committed fingerprints.json — refresh with "
            "tools/lint_programs.py --update if intentional")


# ---------------------------------------------------------------------------
# the configuration matrix: lowered live, linted statically
# ---------------------------------------------------------------------------

class SkipEntry(Exception):
    """Raised by a builder when its environment prerequisite is missing
    (e.g. mesh entries on a single-device host)."""


@dataclass
class MatrixEntry:
    label: str
    build: Callable            # () -> (hlo_text, LintBounds)
    note: str = ""
    # rule id -> reason: known, documented debt.  Waived violations are
    # still reported (status "waived") but do not fail the run; the list
    # of waivers is part of docs/ARCHITECTURE.md's static-analysis
    # section and each one carries a ROADMAP follow-up.
    waive: dict = field(default_factory=dict)


def _bounds_for(spec, access_trips, mesh_exchange=None,
                expect_aliases=None) -> LintBounds:
    ways = spec.assoc or 0
    max_upd = 4 * ways * max(spec.wcols, spec.mcols) if ways else None
    return LintBounds(access_trips=tuple(access_trips), assoc=bool(ways),
                      streams=spec.streams, max_update_elems=max_upd,
                      mesh_exchange=mesh_exchange,
                      expect_aliases=expect_aliases)


def _step_program(cfg_kwargs: dict, donate: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.core.device_simulate import DeviceWTinyLFU
    from repro.kernels.sketch_step import init_step_state, step_ref
    cfg = DeviceWTinyLFU(**cfg_kwargs)
    spec, params = cfg.spec(), cfg.params()
    state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
    shape = (spec.streams, T_STEP) if spec.streams > 1 else (T_STEP,)
    lo = jnp.zeros(shape, jnp.int32)
    jit = jax.jit(step_ref, static_argnums=(0,),
                  donate_argnums=(2,) if donate else ())
    text = jit.lower(spec, params, state, lo, lo).compile().as_text()
    return text, _bounds_for(
        spec, (T_STEP,),
        expect_aliases=len(state) if donate else None)


def _sharded_program(cfg_kwargs: dict):
    import jax.numpy as jnp
    from repro.core.device_simulate import (DeviceWTinyLFU,
                                            _sharded_runner)
    from repro.kernels.sketch_step import init_step_state
    cfg = DeviceWTinyLFU(**cfg_kwargs)
    spec, params = cfg.spec(), cfg.params()
    state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
    los = jnp.zeros((NE_EPOCHS, E_EPOCH), jnp.int32)
    nvalid = jnp.full((NE_EPOCHS,), E_EPOCH, jnp.int32)
    run = _sharded_runner(spec, "jit", False)
    text = run.lower(params, state, los, los,
                     nvalid).compile().as_text()
    return text, _bounds_for(spec, (E_EPOCH,))


def _adaptive_program(cfg_kwargs: dict):
    import jax.numpy as jnp
    from repro.core.device_simulate import (ClimbSpec, DeviceWTinyLFU,
                                            _adaptive_runner,
                                            _climb_carry0)
    from repro.kernels.sketch_step import init_step_state
    cfg = DeviceWTinyLFU(**cfg_kwargs)
    spec, params = cfg.spec(), cfg.params()
    state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
    B = spec.streams
    shape = (NE_EPOCHS, B, E_EPOCH) if B > 1 else (NE_EPOCHS, E_EPOCH)
    los = jnp.zeros(shape, jnp.int32)
    nvalid = jnp.full((NE_EPOCHS,), E_EPOCH, jnp.int32)
    cvec = jnp.asarray(ClimbSpec(epoch_len=E_EPOCH).resolve(cfg))
    carry0 = _climb_carry0(cvec)
    if B > 1:
        carry0 = jnp.broadcast_to(carry0[:, None], (6, B))
    run = _adaptive_runner(spec, "jit", False)
    text = run.lower(params, state, los, los, nvalid, cvec,
                     carry0).compile().as_text()
    return text, _bounds_for(spec, (E_EPOCH,))


def _mesh_program(mode: str):
    import jax
    if jax.device_count() < 2:
        raise SkipEntry(
            "needs >= 2 devices (XLA_FLAGS="
            "--xla_force_host_platform_device_count=2 before jax import)")
    from dataclasses import replace

    import jax.numpy as jnp
    from repro.core.device_simulate import (DeviceWTinyLFU, _mesh_runner,
                                            _to_mesh_state)
    from repro.distributed.mesh import (make_shard_mesh,
                                        mesh_state_shardings)
    from repro.kernels.sketch_step import init_step_state
    cfg = DeviceWTinyLFU(2048, assoc=8, shards=4,
                         mesh=make_shard_mesh(2), mesh_exchange=mode,
                         merge_every=E_EPOCH)
    spec, params = cfg.spec(), cfg.params()
    state = _to_mesh_state(spec, init_step_state(
        replace(spec, mesh_devices=0), cfg.window_cap, cfg.main_cap))
    sh = mesh_state_shardings(cfg.mesh, state.keys())
    state = {k: jax.device_put(v, sh[k]) for k, v in state.items()}
    los = jnp.zeros((NE_EPOCHS, E_EPOCH), jnp.int32)
    tlo = jnp.zeros((T_TAIL,), jnp.int32)
    run = _mesh_runner(spec, cfg.mesh, False)
    text = run.lower(params, state, los, los, tlo,
                     tlo).compile().as_text()
    return text, _bounds_for(spec, (E_EPOCH, T_TAIL),
                             mesh_exchange=mode)


def default_matrix() -> list:
    """The lowered config matrix — flat/assoc x static/adaptive x shards
    x streams x policy x mesh chunk/stale, one representative per axis
    value (the cross product is covered by the per-axis exactness ladder;
    the lint checks structure, which composes)."""
    E = MatrixEntry
    return [
        E("flat-static", lambda: _step_program(dict(capacity=512))),
        E("assoc-static",
          lambda: _step_program(dict(capacity=2048, assoc=8))),
        E("assoc-events",
          lambda: _step_program(dict(capacity=2048, assoc=8, events=True)),
          note="admission event counters in regs"),
        E("assoc-integrity",
          lambda: _sharded_program(
              dict(capacity=2048, assoc=8, shards=4, integrity=True))),
        E("assoc-donated",
          lambda: _step_program(dict(capacity=2048, assoc=8),
                                donate=True),
          note="R5: state donation must alias every leaf"),
        E("flat-streams4",
          lambda: _step_program(dict(capacity=512, streams=B_LANES))),
        E("assoc-streams4",
          lambda: _step_program(
              dict(capacity=512, assoc=8, streams=B_LANES))),
        E("policy-s3fifo",
          lambda: _step_program(
              dict(capacity=2048, assoc=8, policy="s3fifo"))),
        E("policy-arc",
          lambda: _step_program(
              dict(capacity=2048, assoc=8, policy="arc")),
          waive={"R3": "known debt: XLA inserts whole-mtab/ghost copies "
                       "around the ghost-clear fori carry (competitor "
                       "reference path; perf follow-up in ROADMAP)"}),
        E("policy-lfu",
          lambda: _step_program(
              dict(capacity=2048, assoc=8, policy="lfu"))),
        E("assoc-shards4",
          lambda: _sharded_program(
              dict(capacity=2048, assoc=8, shards=4))),
        E("flat-adaptive",
          lambda: _adaptive_program(
              dict(capacity=512, adaptive=True))),
        E("assoc-adaptive",
          lambda: _adaptive_program(
              dict(capacity=2048, assoc=8, adaptive=True))),
        E("assoc-adaptive-streams4",
          lambda: _adaptive_program(
              dict(capacity=512, assoc=8, adaptive=True,
                   streams=B_LANES))),
        E("mesh-chunk", lambda: _mesh_program("chunk"),
          note="needs 2 forced host devices"),
        E("mesh-stale", lambda: _mesh_program("stale"),
          note="needs 2 forced host devices",
          waive={"R3": "known debt: the device-local delta block is "
                       "copied per access inside the shard_map body "
                       "(aliasing breaks across the spmd partitioner; "
                       "perf follow-up in ROADMAP)"}),
    ]


def run_matrix(matrix=None, configs: str | None = None):
    """Lower + lint every matrix entry; returns ``(violations, rows)``
    where rows are report dicts (label, status, counts, seconds)."""
    import time
    matrix = default_matrix() if matrix is None else matrix
    if configs:
        matrix = [e for e in matrix if configs in e.label]
    violations: list[Violation] = []
    rows = []
    for e in matrix:
        t0 = time.monotonic()
        try:
            text, bounds = e.build()
        except SkipEntry as exc:
            rows.append({"label": e.label, "status": "skipped",
                         "reason": str(exc)})
            continue
        v = lint_hlo(text, bounds, config=e.label)
        active = [x for x in v if x.rule not in e.waive]
        waived = [x for x in v if x.rule in e.waive]
        violations += active
        rows.append({"label": e.label,
                     "status": ("fail" if active
                                else "waived" if waived else "ok"),
                     "violations": [x.to_dict() for x in active],
                     "waived": [dict(x.to_dict(),
                                     reason=e.waive[x.rule])
                                for x in waived],
                     "seconds": round(time.monotonic() - t0, 2)})
    return violations, rows
