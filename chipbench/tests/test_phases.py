"""Tests of the phase and host-span readers (``chipbench/phases.py``).

They run on the CPU: hand-built sessions, a CPU profile of the program's
own host spans, the step program's compiled text, and one traced replay of
``kv-64k.ycsbc`` recorded on a TPU v5e (``data/``).
"""
from __future__ import annotations

import copy
import glob
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


phases = _load(os.path.join(BENCH, "phases.py"), "chipbench_phases")
devtrace = phases.devtrace
ACCEPTED = ("step_us_per_access", "entry_host_ms_per_replay",
            "device_idle_share")
NEW = ("step_sketch_us_per_access", "step_tables_us_per_access",
       "step_admission_us_per_access", "entry_span_ms_per_replay")
CHIP = "/device:TPU:0"
SCOPE = "jit(step_ref)/while/body/"


def _accepted(trace, record):
    return {m: _load(os.path.join(BENCH, "metrics", m + ".py"),
                     "metric_" + m).reduce(trace, record) for m in ACCEPTED}


# ---------------------------------------------------------------------------
# hand-built sessions
# ---------------------------------------------------------------------------

def _ops(*evs):
    return [(n, s, d, sc) for n, s, d, sc in evs]


HEAD = [("XLA Modules", [("jit_init(1)", 2, 3, None)]),
        ("XLA Ops", _ops(("%init = s32[8]", 2, 3, "jit(init)/broadcast"),
                         ("%a.1", 10, 2, SCOPE + "sketch/add")))]
MID = [("XLA Ops", _ops(("%a.1", 40, 2, SCOPE + "sketch/add"),
                        ("%r.2", 43, 1, SCOPE + "sketch/reset/while/body/x"),
                        ("%b.3", 46, 2, SCOPE + "admission/argmin"),
                        ("%c.4", 47, 3, SCOPE + "lookup/eq"),
                        ("%w.5", 52, 1, SCOPE + "writes/dus"),
                        ("%l.6", 54, 2, "jit(step_ref)/while/cond/lt")))]
TAIL = [("XLA Modules", [("jit_readback(2)", 90, 2, None)]),
        ("XLA Ops", _ops(("%a.1", 80, 2, SCOPE + "sketch/add"),
                         ("%w.5", 84, 4, SCOPE + "writes/dus"),
                         ("%copy = s32[8]", 90, 2, None)))]
HOST_SPANS = [("simulate_trace.config", 0, 1, None),
              ("simulate_trace.stage_keys", 1, 1, None),
              ("simulate_trace.dispatch", 5, 6, None),
              ("simulate_trace.wait", 11, 78, None),
              ("simulate_trace.readback", 92, 3, None)]


def _sessions(host=True):
    out = {"head": [(CHIP, HEAD)], "mid": [(CHIP, MID)],
           "tail": [(CHIP, TAIL)]}
    if host:
        out["head"].append((phases.HOST, [("simulate_trace",
                                           HOST_SPANS[:3])]))
        out["tail"].append((phases.HOST, [("simulate_trace",
                                           HOST_SPANS[3:])]))
    return copy.deepcopy(out)


REC = {"accesses_per_replay": 4}


def test_condense_keeps_every_value_of_devtrace():
    sess = _sessions()
    old = devtrace.condense(phases.strip(sess), [0, 100], "jit_step_ref")
    new = phases.condense(sess, [0, 100], "jit_step_ref")
    for p, chip in old["chips"].items():
        assert {k: new["chips"][p][k] for k in chip} == chip
    assert new["span"] == old["span"]
    assert _accepted(new, REC) == _accepted(old, REC)
    assert devtrace.summary(old) == devtrace.summary(
        {"span": new["span"], "chips": {p: {k: c[k] for k in old["chips"][p]}
                                        for p, c in new["chips"].items()}})


def test_phases_sum_to_the_middle_sample():
    trace = phases.condense(_sessions(), [0, 100], "jit_step_ref")
    chip = trace["chips"][CHIP]
    ph = chip["phases"]
    # ops [40,42] [43,44] [46,48] [47,50] [52,53] [54,56]: gaps 1, 2, 0,
    # 2, 1 charged to the op that waited; the lookup op adds [48,50]
    assert ph == {"sketch": {"ops_ns": 2, "gap_ns": 0},
                  "sketch/reset": {"ops_ns": 1, "gap_ns": 1},
                  "admission": {"ops_ns": 2, "gap_ns": 2},
                  "lookup": {"ops_ns": 2, "gap_ns": 0},
                  "writes": {"ops_ns": 1, "gap_ns": 2},
                  "unscoped": {"ops_ns": 2, "gap_ns": 1}}
    m = chip["mid"]
    assert sum(v["ops_ns"] + v["gap_ns"] for v in ph.values()) == \
        m["span"][1] - m["span"][0]
    assert sum(v["ops_ns"] for v in ph.values()) == m["busy_ns"]
    v = phases.readers(trace, REC)
    step = _accepted(trace, REC)["step_us_per_access"]
    scale = (88 - 10) / 16 / 1e3 / 4            # step over sample, per acc
    assert v["step_sketch_us_per_access"] == pytest.approx(4 * scale)
    assert v["step_tables_us_per_access"] == pytest.approx(5 * scale)
    assert v["step_admission_us_per_access"] == pytest.approx(4 * scale)
    assert v["step_unscoped_us_per_access"] == pytest.approx(3 * scale)
    assert sum(v[k] for k in v if k.startswith("step_")) == \
        pytest.approx(step)
    # config 1 + stage_keys 1 + dispatch 6 (device busy [5,11]: 6 - 1 + ...)
    spans = {n: (s, e, dev) for n, s, e, dev in trace["host_spans"]}
    assert spans["simulate_trace.dispatch"] == (5, 11, 1)
    assert spans["simulate_trace.readback"] == (92, 95, 0)
    assert v["entry_span_ms_per_replay"] == pytest.approx(
        (1 + 1 + 5 + 3) / 1e6)


def test_summary_names_phases_and_host_spans():
    trace = phases.condense(_sessions(), [0, 100], "jit_step_ref")
    busy, bd = phases.summary(trace)
    assert busy == devtrace.summary(trace)[0]
    ops = [r[0] for r in bd["device_ops"]]
    assert "%c.4 [lookup] (in the step, scaled from the middle sample)" in ops
    gaps = dict((k, v) for k, v in bd["idle_gaps"])
    # idle [0,2]: config [0,1], stage_keys [1,2]; [5,10]: dispatch;
    # [88,90]: wait to 89, then no span; [92,100]: readback to 95, no span
    assert gaps["simulate_trace.config"] == pytest.approx(1e-9)
    assert gaps["simulate_trace.dispatch"] == pytest.approx(5e-9)
    assert gaps["simulate_trace.wait"] == pytest.approx(1e-9)
    assert gaps["simulate_trace.readback"] == pytest.approx(3e-9)
    assert gaps["outside the program's spans (harness)"] == \
        pytest.approx(6e-9)
    assert any(k.startswith("inside the step program") for k in gaps)
    assert sum(o + g for _, o, g in bd["phases"]) == pytest.approx(78e-9)


def test_outside_phases_are_read_once_and_the_rest_scaled():
    """``probes`` in the head and ``layout`` in the tail are charged as
    read; the placed step less them is split by the middle sample, so the
    phases sum to the placed step."""
    sess = _sessions()
    sess["head"][0][1][1][1].append(("%p.7", 9, 1, "jit(step_ref)/probes/mul"))
    sess["tail"][0][1][1][1].append(("%y.8", 88, 1,
                                     "jit(step_ref)/layout/reshape"))
    trace = phases.condense(sess, [0, 100], "jit_step_ref")
    chip = trace["chips"][CHIP]
    assert chip["step"] == [9, 89]
    assert chip["outside"] == {"probes": {"ops_ns": 1, "gap_ns": 0},
                               "layout": {"ops_ns": 1, "gap_ns": 0}}
    v = phases.readers(trace, REC)
    scale = (80 - 2) / 16 / 1e3 / 4
    assert v["step_sketch_us_per_access"] == pytest.approx(4 * scale)
    assert v["step_unscoped_us_per_access"] == pytest.approx(
        3 * scale + 2 / 1e3 / 4)
    assert sum(v[k] for k in v if k.startswith("step_")) == pytest.approx(
        _accepted(trace, REC)["step_us_per_access"])
    _, bd = phases.summary(trace)
    rows = {k: (o, g) for k, o, g in bd["phases"]}
    assert rows["probes"] == rows["layout"] == (1e-9, 0.0)
    assert sum(o + g for o, g in rows.values()) == pytest.approx(80e-9)


def test_thin_cuts_past_the_step_programs_own_start():
    """An op of another program that sticks out of its module places the
    step early; the fixture's head still keeps the step's first ops."""
    sess = _sessions()
    mods, ops = sess["head"][0][1]
    mods[1].append(("jit_step_ref(7)", 10, 80, None))
    ops[1].append(("%x.9", 4, 2, None))         # out of jit_init's [2, 5]
    trace = devtrace.condense(phases.strip(sess), [0, 100], "jit_step_ref")
    assert trace["chips"][CHIP]["step"] == [4, 88]
    kept = phases.thin(sess, trace, "jit_step_ref", keep_ms=3e-6)
    head = [n for ln, evs in kept["head"][0][1] if ln == "XLA Ops"
            for n, *_ in evs]
    assert head == ["%init", "%a.1", "%x.9"]


def test_ops_missing_from_the_compiled_text_fail():
    """Names taken from another program than the one that ran: an op of
    the step with no entry beside ops with one raises, and so does one
    with none where scopes are required."""
    sess = _sessions()
    n, s, d, _ = sess["mid"][0][1][0][1][2]
    sess["mid"][0][1][0][1][2] = (n, s, d, None)
    with pytest.raises(ValueError, match="1 of 6 ops"):
        phases.condense(sess, [0, 100], "jit_step_ref")
    bare = _sessions(host=False)
    for planes in bare.values():
        for _, lines in planes:
            for i, (ln, evs) in enumerate(lines):
                lines[i] = (ln, [(n, s, d, None) for n, s, d, _ in evs])
    assert phases.condense(bare, [0, 100], "jit_step_ref")["chips"][CHIP][
        "phases"] is None
    with pytest.raises(ValueError, match="6 of 6 ops"):
        phases.condense(bare, [0, 100], "jit_step_ref", require_scopes=True)


def test_readers_give_nothing_without_scopes_or_spans():
    """A program without the scopes and spans (the parent's) still reads
    the accepted metrics; the new readers give None."""
    sess = _sessions(host=False)
    for planes in sess.values():
        for _, lines in planes:
            for i, (ln, evs) in enumerate(lines):
                lines[i] = (ln, [(n, s, d, "jit(step_ref)/while/body/add")
                                 for n, s, d, _ in evs])
    trace = phases.condense(sess, [0, 100], "jit_step_ref")
    assert trace["chips"][CHIP]["phases"] is None
    v = phases.readers(trace, REC)
    assert all(v[k] is None for k in NEW)
    assert _accepted(trace, REC)["step_us_per_access"] is not None
    phases.summary(trace)


@pytest.mark.parametrize("scope,phase", [
    (SCOPE + "sketch/reset/while/body/dynamic_slice", "sketch/reset"),
    (SCOPE + "sketch/and", "sketch"),
    (SCOPE + "window/argmin", "window"),
    (SCOPE + "slru/jit(_where)/select_n", "slru"),
    ("jit(step_ref)/probes/mul", "probes"),
    ("jit(step_ref)/while/body/add", "unscoped"), (None, "unscoped")])
def test_phase_of(scope, phase):
    assert phases.phase_of(scope) == phase


# ---------------------------------------------------------------------------
# the program's own spans and scopes, on the CPU
# ---------------------------------------------------------------------------

def test_compiled_step_carries_every_phase_scope():
    kw = {"capacity": 512, "assoc": 8, "backend": "jit"}
    text = phases.step_text(kw, np.arange(64, dtype=np.uint64), 0, True)
    names = phases.op_names(text)
    found = {phases.phase_of(s) for s in names.values()}
    assert {"sketch", "sketch/reset", "lookup", "window", "slru",
            "admission", "writes", "bookkeeping", "probes",
            "layout"} <= found
    # every instruction is named, with or without an op_name
    assert len(names) == text.count(" = ") - text.count('" = "')
    with pytest.raises(ValueError, match="backend='jit'"):
        phases.step_text(dict(kw, backend="pallas"), np.arange(64), 0, True)


def test_simulate_trace_writes_its_host_spans(tmp_path):
    import jax
    from repro.core.device_simulate import simulate_trace
    keys = np.arange(256, dtype=np.uint64)
    simulate_trace(keys, 64, assoc=8)                    # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        simulate_trace(keys, 64, assoc=8)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    sess = phases.read_sessions({"head": path})
    (plane, [(line, spans)]), = sess["head"]
    assert (plane, line) == (phases.HOST, "simulate_trace")
    spans.sort(key=lambda x: x[1])
    assert [n for n, *_ in spans] == [phases.HOST_PREFIX + p for p in (
        "config", "init_state", "stage_keys", "dispatch", "wait",
        "readback")]
    for (_, s, d, _), (_, s2, _, _) in zip(spans, spans[1:]):
        assert s + d <= s2


# ---------------------------------------------------------------------------
# one traced replay of kv-64k.ycsbc, recorded on a TPU v5e and thinned
# ---------------------------------------------------------------------------

FIXTURE = os.path.join(HERE, "data", "kv-64k.ycsbc.trace.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def _condense(rec, sessions=None):
    return phases.condense(sessions or rec["sessions"], rec["span"],
                           rec["step_program"])


def test_recorded_replay_accepted_metrics_read_the_same(recorded):
    """The parent's reduction of the stripped sessions and the new one read
    the same three accepted metrics; they and the new readers read the
    values the chip run printed from its whole sessions (``values``).

    The thinned head holds no ``probes`` ops: the recording placed the
    step's start at another program's op 22.9 ms before the step's own,
    and cut the head 1 ms past that (``thin`` now cuts past the step's own
    module event).  ``outside`` holds what the chip run read from its
    whole sessions; the thinned tail reads its ``layout`` exactly."""
    old = devtrace.condense(phases.strip(recorded["sessions"]),
                            recorded["span"], recorded["step_program"])
    new = _condense(recorded)
    rec = recorded["record"]
    assert _accepted(new, rec) == _accepted(old, rec) == \
        {k: recorded["values"][k] for k in ACCEPTED}
    for p, chip in new["chips"].items():
        whole = recorded["outside"][p]
        assert chip["outside"] == {"layout": whole["layout"]}
        chip["outside"] = whole
    assert phases.readers(new, rec) == {k: recorded["values"][k]
                                        for k in phases.readers(new, rec)}


def test_recorded_replay_phases_sum_to_the_sample(recorded):
    """Every op of the middle sample has a scope path; its phases sum to
    its span, and with ``probes`` and ``layout`` read from the head and
    the tail, the breakdown's phases sum to the placed step."""
    trace = phases.condense(recorded["sessions"], recorded["span"],
                            recorded["step_program"], require_scopes=True)
    for chip in trace["chips"].values():
        ph, m = chip["phases"], chip["mid"]
        assert set(ph) >= {"sketch", "lookup", "window", "slru",
                           "admission", "writes", "bookkeeping"}
        assert sum(v["ops_ns"] + v["gap_ns"] for v in ph.values()) == \
            m["span"][1] - m["span"][0]
        assert sum(v["ops_ns"] for v in ph.values()) == m["busy_ns"]
        assert set(chip["outside"]) == {"layout"}
        # the head's ops of other programs inside the placed step (above)
        assert chip["unmapped"] == 2
    v = phases.readers(trace, recorded["record"])
    step = _accepted(trace, recorded["record"])["step_us_per_access"]
    assert sum(v[k] for k in v if k.startswith("step_")) == \
        pytest.approx(step, rel=1e-9)
    _, bd = phases.summary(trace)
    placed = [c["step"][1] - c["step"][0] for c in trace["chips"].values()]
    assert sum(o + g for _, o, g in bd["phases"]) == pytest.approx(
        sum(placed) / len(placed) / 1e9, rel=1e-9)


def test_recorded_replay_without_scopes_or_spans(recorded):
    """Scopes and host spans stripped, as a program without them records:
    the new readers give None, the accepted ones read as before, and the
    breakdown is still made."""
    bare = {tag: [(p, [(ln, [(n, s, d, None) for n, s, d, _ in evs])
                       for ln, evs in lines])
                  for p, lines in planes if p != phases.HOST]
            for tag, planes in recorded["sessions"].items()}
    trace = _condense(recorded, bare)
    rec = recorded["record"]
    assert all(v is None for v in phases.readers(trace, rec).values())
    assert _accepted(trace, rec) == {k: recorded["values"][k]
                                     for k in ACCEPTED}
    busy, bd = phases.summary(trace)
    assert busy == devtrace.summary(trace)[0] and bd["device_ops"]
