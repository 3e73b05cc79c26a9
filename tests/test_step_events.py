"""Admission event counters (``StepSpec.events``): the program's own
counts of candidates, admissions, rejections and §3.3 resets.

The counts are held against the plain reference of the chip benchmark
(``chipbench/configs/wtinylfu_ref.py``, which shares no code with the
engine), and switching them on must change nothing else a run returns.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from repro.core.device_simulate import (DeviceWTinyLFU, simulate_sweep,
                                        simulate_trace)
from repro.traces.synthetic import zipf_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "wtinylfu_ref", os.path.join(ROOT, "chipbench", "configs",
                                 "wtinylfu_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

C, ASSOC, SF, WARMUP = 512, 8, 8, 1000
KW = dict(assoc=ASSOC, sample_factor=SF, window_frac=0.01)


@pytest.fixture(scope="module")
def trace():
    # 12,000 accesses cross the first reset at 8 C = 4,096 additions and
    # several more after it
    return zipf_trace(12_000, n_items=8192, alpha=0.9, seed=[13, 1])


@pytest.fixture(scope="module")
def runs(trace):
    on = simulate_trace(trace, C, warmup=WARMUP, return_state=True,
                        events=True, **KW)
    off = simulate_trace(trace, C, warmup=WARMUP, return_state=True, **KW)
    want = ref.simulate(trace, ref.geometry(C, ASSOC, SF, 0.01), WARMUP)
    return on, off, want


def test_event_counts_equal_the_reference(runs):
    (res, _, _), _, want = runs
    ev = res.extra["events"]
    assert set(ev) == {"candidates", "admitted", "rejected", "resets"}
    assert want["resets"] >= 2 and want["admitted"] > 0 < want["rejected"]
    assert ev["admitted"] == want["admitted"]
    assert ev["rejected"] == want["rejected"]
    assert ev["resets"] == want["resets"]
    assert ev["candidates"] >= ev["admitted"] + ev["rejected"]


def test_events_change_nothing_else(runs):
    (r1, s1, h1), (r0, s0, h0), want = runs
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h0))
    np.testing.assert_array_equal(np.asarray(h1), want["hits"])
    for k in ("counters", "doorkeeper", "wtab", "mtab"):
        np.testing.assert_array_equal(np.asarray(s1[k]), np.asarray(s0[k]))
    np.testing.assert_array_equal(np.asarray(s1["regs"])[:8],
                                  np.asarray(s0["regs"]))
    assert r1.hits == r0.hits and "events" not in r0.extra


def test_event_counts_sum_over_lanes(trace):
    lanes = np.stack([trace[:6000], trace[6000:]])
    res = simulate_trace(lanes, C, streams=2, events=True, **KW)
    one = [simulate_trace(t, C, events=True, **KW).extra["events"]
           for t in lanes]
    assert res.extra["events"] == {k: one[0][k] + one[1][k]
                                   for k in one[0]}


def test_pallas_backend_counts_the_same(trace):
    short = trace[:1500]
    got = simulate_trace(short, 64, backend="pallas", chunk=256,
                         events=True, **KW)
    want = simulate_trace(short, 64, events=True, **KW)
    assert got.extra["events"] == want.extra["events"]
    assert got.hits == want.hits


def test_checkpointed_run_returns_the_counts(trace, tmp_path):
    cfg = DeviceWTinyLFU(C, events=True, **KW)
    whole = simulate_trace(trace, C, events=True, **KW).extra["events"]
    res = cfg.run(trace, checkpoint_dir=str(tmp_path),
                  checkpoint_every=4096)
    assert res.extra["events"] == whole


@pytest.mark.parametrize("kw", [
    dict(assoc=None), dict(assoc=8, policy="s3fifo"),
    dict(assoc=8, shards=2), dict(assoc=8, adaptive=True)],
    ids=["flat", "s3fifo", "sharded", "adaptive"])
def test_events_on_an_unsupported_layout_names_the_field(kw):
    with pytest.raises(ValueError, match="events"):
        DeviceWTinyLFU(C, events=True, **kw)


def test_sweep_refuses_events():
    with pytest.raises(ValueError, match="events"):
        simulate_sweep(np.arange(64), [C], assoc=8, events=True)


def test_events_off_is_the_identical_program():
    from repro.analysis.program_lint import assert_identical_program
    assert_identical_program("events-off")


def test_events_on_lowers_a_different_program():
    from repro.analysis.program_lint import pin_program_text
    assert pin_program_text(events=True) != pin_program_text()


def test_events_on_program_lints_clean():
    """The counters keep the step's in-place write discipline (R0-R6)."""
    from repro.analysis.program_lint import default_matrix, run_matrix
    entry = [e for e in default_matrix() if e.label == "assoc-events"]
    violations, rows = run_matrix(entry)
    assert not violations, [str(v) for v in violations]
    assert [r["status"] for r in rows] == ["ok"]
