#!/usr/bin/env python3
"""Phases of the per-access step and host spans of the entry, read from the
profiler sessions of one traced replay.

    python3 chipbench/phases.py --workload <cell> --seed <n> [--events 0|1]
                                [--fixture <path>]

The program names its own work:

- ``jax.named_scope`` phases in ``kernels/sketch_step._one_access_set``:
  ``sketch`` (its §3.3 word loops under ``sketch/reset``), ``lookup``,
  ``window``, ``slru``, ``admission``, ``writes``, ``bookkeeping``; and in
  ``step_ref`` outside the scan, ``probes`` and ``layout``.  Each lands in
  the HLO ``op_name`` of the ops the phase produced;
- ``jax.profiler.TraceAnnotation`` host spans in
  ``core/device_simulate.simulate_trace``: ``simulate_trace.config``,
  ``.init_state``, ``.stage_keys``, ``.dispatch``, ``.wait``,
  ``.readback``.  They land on the ``/host:CPU`` plane of the same
  sessions as the TPU ops, with starts on the same base.  A span open
  across two sessions is kept by neither: ``wait`` shows only in a
  profile that covers the whole replay.

The scope path of a TPU op is the ``op_name`` of the HLO instruction of
that name in the step program's compiled text (:func:`op_names`): a TPU
v5e under jax 0.9.0 gives its op events no ``tf_op`` stat.  An op of the
step that the text does not hold is an error (:func:`condense`), not an
unscoped op.

:func:`condense` returns what ``devtrace.condense`` returns, key for key
and value for value, and adds, per chip:

- ``phases``: the middle sample's op time and gap time per phase.  Each op
  is charged to the innermost phase in its path, or to ``unscoped``, and
  the gap since the coverage before it to the op that waited, so the
  phases sum to the sample's span;
- ``outside``: the same for the ``probes`` ops the head session holds and
  the ``layout`` ops the tail holds, once per replay and not scaled;
- ``op_phase``;

and ``host_spans``, each with the device time inside it.  The placed step
less ``outside`` is split by the middle sample's shares, so ``outside``
and the scaled phases sum to the placed step.  The readers
(:data:`STEP_METRICS`, :func:`entry_span_ms_per_replay`) give None where
the program has no such scopes or spans, as a program before them has not.

Run as a script, it is a recording tool: it replays one trace of the
cell's pool through the program with ``events`` as given (a warm replay
that compiles, one that times the sessions, then one under the three
sessions of ``devtrace.Sessions``) and prints one JSON line: the accepted
per-layer metrics and the new readers, the breakdown, and the program's
admission counts beside the plain reference's.  ``--fixture`` writes the
traced replay's sessions, thinned by :func:`thin`, with the values the
readers took from the whole sessions.  Throughput figures come from the
harness (``run.py``), not from this script.
"""
from __future__ import annotations

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
HOST = "/host:CPU"
HOST_PREFIX = "simulate_trace."
SCOPES = ("sketch", "lookup", "window", "slru", "admission", "writes",
          "bookkeeping", "probes", "layout")
# phases outside the scan, once per replay: the session that holds them
OUTSIDE = {"probes": "head", "layout": "tail"}
UNSCOPED = "unscoped"
# per-layer readers of the step: metric -> the phases whose time it sums
STEP_METRICS = {
    "step_sketch_us_per_access": ("sketch", "sketch/reset"),
    "step_tables_us_per_access": ("lookup", "window", "slru", "writes",
                                  "bookkeeping"),
    "step_admission_us_per_access": ("admission",),
}
# host spans whose time is the entry's own (``wait`` is the device's)
ENTRY_SPANS = tuple(HOST_PREFIX + p for p in ("config", "init_state",
                                              "stage_keys", "dispatch",
                                              "readback"))
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def _load(name: str):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


devtrace = _load("devtrace")


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled HLO module's text;
    ``""`` for an instruction the compiler made without one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            o = _OP_NAME.search(line)
            out[m.group(1)] = o.group(1) if o else ""
    return out


def phase_of(scope: str | None) -> str:
    """The innermost phase scope of an op's path, or ``unscoped``."""
    parts = scope.split("/") if scope else []
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] == "reset" and i and parts[i - 1] == "sketch":
            return "sketch/reset"
        if parts[i] in SCOPES:
            return parts[i]
    return UNSCOPED


def read_sessions(paths: dict, names: dict | None = None) -> dict:
    """As ``devtrace.read_sessions``, each event with a fourth element, and
    one more plane: ``{tag: [(plane, [(line, [(event, start_ns,
    duration_ns, scope)])])]}``.  ``scope`` is a TPU op's scope path, its
    instruction's entry in ``names`` (:func:`op_names`), and None on
    module events and on an op the text does not hold (every op where
    ``names`` is empty); the ``/host:CPU`` plane holds one line,
    ``simulate_trace``, of the program's host spans.  Starts are made
    absolute exactly as ``devtrace.read_sessions`` makes them."""
    import jax
    names = names or {}
    out = {}
    for tag, path in paths.items():
        pd = jax.profiler.ProfileData.from_file(path)
        t0 = next((dict(p.stats)["profile_start_time"] for p in pd.planes
                   if p.name == "Task Environment"), 0)
        planes = []
        for p in pd.planes:
            if p.name.startswith("/device:TPU:"):
                planes.append((p.name, [
                    (ln.name, [(e.name, t0 + e.start_ns, e.duration_ns,
                                names.get(e.name.split(" = ")[0].lstrip("%"))
                                if ln.name == "XLA Ops" else None)
                               for e in ln.events])
                    for ln in p.lines
                    if ln.name in ("XLA Modules", "XLA Ops")]))
            elif p.name == HOST:
                planes.append((HOST, [("simulate_trace", [
                    (e.name, t0 + e.start_ns, e.duration_ns, None)
                    for ln in p.lines for e in ln.events
                    if e.name.startswith(HOST_PREFIX)])]))
        out[tag] = planes
    return out


def strip(sessions: dict) -> dict:
    """The sessions as ``devtrace.read_sessions`` gives them: TPU planes
    only, events without their scope."""
    return {tag: [(p, [(ln, [tuple(e[:3]) for e in evs])
                       for ln, evs in lines])
                  for p, lines in planes if p.startswith("/device:TPU:")]
            for tag, planes in sessions.items()}


def _step_ops(sessions: dict, tag: str, pname: str, placed: list) -> list:
    """One session's ops of one chip inside the placed step, as
    ``devtrace.condense`` selects the middle sample's:
    ``(start, end, name, scope)``."""
    return [(s, s + d, n.split(" = ")[0], sc)
            for p, lines in sessions.get(tag, []) if p == pname
            for ln, evs in lines if ln == "XLA Ops"
            for n, s, d, sc in evs
            if placed[0] <= s and s + d <= placed[1]]


def _check_mapped(ops: list, pname: str, require: bool) -> bool:
    """Whether the step's ops carry scope paths.  Some with and some
    without means the names were taken from another program: an error, as
    is none with ``require``."""
    lost = sum(sc is None for *_, sc in ops)
    if lost and (require or lost < len(ops)):
        raise ValueError(f"{pname}: {lost} of {len(ops)} ops of the step "
                         "are not in the step program's compiled text")
    return bool(ops) and not lost


def attribute(ops: list) -> dict:
    """``{phase: {"ops_ns", "gap_ns"}}`` of ``(start, end, name, phase)``
    ops.  In start order, each op is charged the time it adds to the
    coverage so far and the gap before it, so the phases sum to the span
    from the first start to the last end, and the ops to its busy time."""
    out, cur = {}, None
    for s, e, _, ph in sorted(ops):
        r = out.setdefault(ph, {"ops_ns": 0, "gap_ns": 0})
        if cur is None:
            r["ops_ns"] += e - s
            cur = e
            continue
        r["gap_ns"] += max(0, s - cur)
        r["ops_ns"] += max(0, e - max(s, cur))
        cur = max(cur, e)
    return out


def condense(sessions: dict, span: list, step: str,
             require_scopes: bool = False) -> dict:
    """``devtrace.condense`` of the stripped sessions, with ``phases``,
    ``outside`` and ``op_phase`` on each chip (None where no op of the
    middle sample has a phase scope) and the top-level ``host_spans``:
    ``[name, start, end, device_ns]``, ``device_ns`` the device's busy time
    inside the span, averaged over the chips whose step program was
    placed.  ``outside`` holds the phases of :data:`OUTSIDE` found in their
    session; None where there are none.  An op of the middle sample
    without a scope path, beside ops with one, raises; so does any without
    one under ``require_scopes``.  In the head and the tail, where another
    program's ops can fall inside the placed step, such ops are counted
    (``unmapped``) and charged to no ``outside`` phase."""
    trace = devtrace.condense(strip(sessions), span, step)
    for pname, chip in trace["chips"].items():
        chip["phases"] = chip["outside"] = chip["op_phase"] = None
        chip["unmapped"] = None
        if not chip["step"] or not chip["mid"]:
            continue
        mid = _step_ops(sessions, "mid", pname, chip["step"])
        if not _check_mapped(mid, pname, require_scopes):
            continue
        ops = [(s, e, n, phase_of(sc)) for s, e, n, sc in mid]
        if any(ph != UNSCOPED for *_, ph in ops):
            chip["phases"] = attribute(ops)
            chip["op_phase"] = {n: ph for _, _, n, ph in ops}
            out, chip["unmapped"] = {}, 0
            for tag in set(OUTSIDE.values()):
                edge = _step_ops(sessions, tag, pname, chip["step"])
                chip["unmapped"] += sum(sc is None for *_, sc in edge)
                out.update((ph, v) for ph, v in attribute(
                    [(s, e, n, phase_of(sc)) for s, e, n, sc in edge]
                ).items() if OUTSIDE.get(ph) == tag)
            chip["outside"] = out or None
    placed = [c for c in trace["chips"].values() if c["step"]]
    seen = {(n, s): d for planes in sessions.values()
            for p, lines in planes if p == HOST
            for _, evs in lines for n, s, d, _ in evs}
    trace["host_spans"] = [
        [n, s, s + d, sum(devtrace.overlap(c["busy"], s, s + d)
                          for c in placed) / len(placed) if placed else 0.0]
        for (n, s), d in sorted(seen.items(), key=lambda x: x[0][1])]
    return trace


def _scaled(chip: dict):
    """``(scale, outside)``: ns of the placed step per ns of the middle
    sample, for the step less its ``outside`` phases, and those phases'
    ``{phase: ns}``; None without phases."""
    m, ph = chip["mid"], chip.get("phases")
    if not chip["step"] or not m or not ph or m["span"][1] <= m["span"][0]:
        return None
    out = {k: v["ops_ns"] + v["gap_ns"]
           for k, v in (chip.get("outside") or {}).items()}
    rest = chip["step"][1] - chip["step"][0] - sum(out.values())
    return rest / (m["span"][1] - m["span"][0]), out


def step_us_per_phase(trace: dict, record: dict, phases) -> float | None:
    """Device microseconds per access of ``phases`` in the step: their
    share of the middle sample's span (ops and gaps) times the placed
    step less its ``outside`` phases, plus those of ``phases`` among them,
    over the replay's accesses, averaged over the traced chips."""
    per = []
    for c in trace["chips"].values():
        sc = _scaled(c)
        if sc is None:
            continue
        scale, out = sc
        t = sum(v["ops_ns"] + v["gap_ns"] for k, v in c["phases"].items()
                if k in phases) * scale
        t += sum(v for k, v in out.items() if k in phases)
        per.append(t / 1e3 / record["accesses_per_replay"])
    return sum(per) / len(per) if per else None


def entry_span_ms_per_replay(trace: dict, record: dict) -> float | None:
    """Milliseconds of the entry's own host spans (``config``,
    ``init_state``, ``stage_keys``, ``dispatch``, ``readback``) less the
    device time inside them; None without such spans."""
    spans = [x for x in trace.get("host_spans") or [] if x[0] in ENTRY_SPANS]
    if not spans or not any(c["step"] for c in trace["chips"].values()):
        return None
    return sum(e - s - dev for _, s, e, dev in spans) / 1e6


def readers(trace: dict, record: dict) -> dict:
    """Every reader of this module: ``{name: value or None}``, with the
    rest of the step (breakdown only): ``unscoped``, ``probes`` and
    ``layout``, so that the four sum to ``step_us_per_access``."""
    out = {k: step_us_per_phase(trace, record, v)
           for k, v in STEP_METRICS.items()}
    named = {p for v in STEP_METRICS.values() for p in v}
    out["step_unscoped_us_per_access"] = None
    if out["step_sketch_us_per_access"] is not None:
        out["step_unscoped_us_per_access"] = step_us_per_phase(
            trace, record, {p for c in trace["chips"].values()
                            for p in {**(c.get("phases") or {}),
                                      **(c.get("outside") or {})}} - named)
    out["entry_span_ms_per_replay"] = entry_span_ms_per_replay(trace, record)
    return out


def _host_label(spans: list, a, b) -> list:
    """``[(label, ns)]`` of the idle interval ``[a, b]``, cut where the host
    spans that cover it begin and end; each piece takes the shortest span
    covering it, or "outside the program's spans (harness)"."""
    cuts = sorted({a, b} | {x for _, s, e, _ in spans for x in (s, e)
                            if a < x < b})
    out = []
    for x, y in zip(cuts, cuts[1:]):
        cover = [(e - s, n) for n, s, e, _ in spans if s <= x and y <= e]
        out.append((min(cover)[1] if cover else
                    "outside the program's spans (harness)", y - x))
    return out


def summary(trace: dict) -> tuple[dict, dict]:
    """``devtrace.summary``'s busy time, and its breakdown with each op of
    the step tagged with its phase (``concatenate.139 [admission]``), each
    idle gap outside the step named by the host span that covers it, and
    two more lists: ``phases`` ([phase, ops_s, gaps_s] over the whole
    placed step: the ``outside`` phases as read, the rest scaled from the
    middle sample) and ``host_spans`` ([name, s, device_s])."""
    busy, bd = devtrace.summary(trace)
    tag = " (in the step"
    n = len(trace["chips"])
    phases, gaps = {}, {}
    for c in trace["chips"].values():
        op_phase = c.get("op_phase") or {}
        for row in bd["device_ops"]:
            op = row[0].split(tag)[0]
            if tag in row[0] and op in op_phase:
                row[0] = row[0].replace(op, f"{op} [{op_phase[op]}]", 1)
        sc = _scaled(c)
        if sc is not None:
            rows = [(k, v, sc[0]) for k, v in c["phases"].items()]
            rows += [(k, v, 1.0)
                     for k, v in (c.get("outside") or {}).items()]
            for k, v, scale in rows:
                o, g = phases.get(k, (0.0, 0.0))
                phases[k] = (o + v["ops_ns"] * scale / 1e9 / n,
                             g + v["gap_ns"] * scale / 1e9 / n)
        spans = trace.get("host_spans") or []
        s0, s1 = trace["span"]
        iv = [[max(x, s0), min(y, s1)] for x, y in c["busy"]
              if y > s0 and x < s1]
        edges = [s0] + [x for xy in iv for x in xy] + [s1]
        for x, y in zip(edges[::2], edges[1::2]):
            for label, ns in _host_label(spans, x, y) if y > x else []:
                gaps[label] = gaps.get(label, 0.0) + ns / 1e9 / n
    inner = [r for r in bd["idle_gaps"] if r[0].startswith("inside")]
    bd["idle_gaps"] = sorted(inner + [[k, v] for k, v in gaps.items()],
                             key=lambda x: -x[1])[:10]
    bd["phases"] = sorted(([k, o, g] for k, (o, g) in phases.items()),
                          key=lambda x: -(x[1] + x[2]))
    bd["host_spans"] = [[nm, (e - s) / 1e9, dev / 1e9]
                        for nm, s, e, dev in trace.get("host_spans") or []]
    return busy, bd


def thin(sessions: dict, trace: dict, step_program: str,
         keep_ms: float = 1.0, mid_ops: int = 5000) -> dict:
    """The sessions cut to what a fixture keeps: every module event and
    host span; head ops up to ``keep_ms`` past the step's first op, tail
    ops from ``keep_ms`` before its last, each end taken from the step
    program's own module event where the session holds one (the placement
    can take another program's op whose rounded times stick out of its
    module); the first ``mid_ops`` ops of the middle session inside the
    step; every op with its scope path.  An op keeps the name the
    reductions read, the instruction's (``%fusion.187``), not its whole
    HLO text."""
    keep = keep_ms * 1e6
    out = {}
    for tag, planes in sessions.items():
        new = []
        for p, lines in planes:
            step = trace["chips"].get(p, {}).get("step")
            if step:
                own = [(s, s + d) for ln, evs in lines
                       if ln == "XLA Modules" for n, s, d, *_ in evs
                       if n.startswith(step_program)]
                first = max(step[0], min((a for a, _ in own),
                                         default=step[0]))
                last = min(step[1], max((b for _, b in own),
                                        default=step[1]))
            kept = []
            for ln, evs in lines:
                if ln == "XLA Ops":
                    evs = [(n.split(" = ")[0], s, d, sc)
                           for n, s, d, sc in evs]
                if ln == "XLA Ops" and step:
                    if tag == "head":
                        evs = [e for e in evs if e[1] <= first + keep]
                    elif tag == "tail":
                        evs = [e for e in evs if e[1] + e[2] >= last - keep]
                    else:
                        evs = sorted((e for e in evs if step[0] <= e[1]
                                      and e[1] + e[2] <= step[1]),
                                     key=lambda e: e[1])[:mid_ops]
                kept.append((ln, evs))
            new.append((p, kept))
        out[tag] = new
    return out


def step_text(kwargs: dict, trace, warmup: int, events: bool) -> str:
    """Compiled text of the step program ``simulate_trace(trace,
    warmup=warmup, events=events, **kwargs)`` runs: the same program,
    built from the same arguments and staged keys, so the same
    instruction names (a warm compile cache hands back the very
    executable).  Only the single-device ``jit`` scan is read; any other
    path raises."""
    import numpy as np
    from repro.core.device_simulate import (DeviceWTinyLFU, _jit_step,
                                            _trace_lanes)
    from repro.kernels.sketch_step import init_step_state
    kw = dict(kwargs, events=events)
    backend = kw.pop("backend", "jit")
    cfg = DeviceWTinyLFU(**kw)
    if (backend != "jit" or cfg.adaptive or cfg.shards > 1
            or cfg.mesh is not None):
        raise ValueError("phases: only the single-device backend='jit' "
                         "scan is read; this configuration runs another "
                         "program")
    spec = cfg.spec()
    state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
    lo, hi = _trace_lanes(np.asarray(trace))
    return _jit_step.lower(spec, cfg.params(warmup=warmup), state, lo,
                           hi).compile().as_text()


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import time
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--events", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    run = _load("run")
    c = run.load_cell(args.workload, ROOT)
    cfg, mix = c["config"], c["mix"]
    name = c["cell"]["name"]

    def log(*a):
        print(f"[phases {name} events={args.events}]", *a, file=sys.stderr,
              flush=True)

    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(ROOT, ".chipbench_trace", "logs"))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"phases: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.core.device_simulate import simulate_trace
    warmup = int(mix.get("warmup", 0))
    kwargs = dict(cfg["kwargs"], events=bool(args.events))

    def write_fixture(path, raw, span, values):
        step = devtrace.condense(strip(raw), span, cfg["step_program"])
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"cell": name, "seed": args.seed,
                       "events": args.events, "span": span,
                       "step_program": cfg["step_program"],
                       "record": {"accesses_per_replay": units},
                       "values": values,
                       "sessions": thin(raw, step, cfg["step_program"])}, f,
                      separators=(",", ":"))

    def call(keys):
        out = simulate_trace(keys, warmup=warmup, return_state=True,
                             **kwargs)
        jax.block_until_ready(out[1:])
        return out

    pool = c["generate"].generate(mix, args.seed,
                                  os.path.join(HERE, "traffic"))
    t = time.perf_counter()
    call(pool[-1])
    log(f"warm replay (compiles) {time.perf_counter() - t!r} s")
    t = time.perf_counter()
    call(pool[1 % len(pool)])
    replay_s = time.perf_counter() - t
    units = c["entry"].units(pool[0])
    log(f"replay that times the sessions {replay_s!r} s")

    trace_dir = os.path.join(ROOT, ".chipbench_trace", "phases")
    shutil.rmtree(trace_dir, ignore_errors=True)
    sessions = devtrace.Sessions(jax, trace_dir, replay_s)
    s0 = time.time_ns()
    res, state, hits = call(pool[0])
    span = [s0, time.time_ns()]
    files = sessions.close()
    raw = read_sessions(files, op_names(step_text(
        cfg["kwargs"], pool[0], warmup, bool(args.events))))
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.fixture:                    # kept even if a reduction fails
        write_fixture(args.fixture, raw, span, None)
    trace = condense(raw, span, cfg["step_program"], require_scopes=True)
    if not trace["chips"] or not all(ch["step"] for ch in
                                     trace["chips"].values()):
        raise RuntimeError("traced replay: the step program was not placed")
    busy, breakdown = summary(trace)
    record = {"accesses_per_replay": units}
    metrics = {m["name"]: mod.reduce(trace, record)
               for m, mod in c["per_layer"]}
    metrics.update(readers(trace, record))
    log(f"metrics {json.dumps(metrics)}")

    lost = [ch.get("unmapped") for ch in trace["chips"].values()]
    log("ops of the head and tail inside the step that the compiled text "
        f"does not hold: {lost}")
    got = c["entry"].readback((res, state, hits))
    num, fill = c["reference"].compare(cfg["kwargs"], mix, pool[0], got)
    log(f"program events {json.dumps(res.extra.get('events'))}; reference "
        f"{json.dumps(fill)}; compared {json.dumps(num)}")
    if args.fixture:
        write_fixture(args.fixture, raw, span, metrics)
    print(json.dumps({
        "cell": name, "seed": args.seed, "events": args.events,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "busy": busy, "metrics": metrics, "breakdown": breakdown,
        "program_events": res.extra.get("events"), "reference": fill,
        "compared": num}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
