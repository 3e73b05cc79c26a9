"""Tests of the chip benchmark harness.  They run on the CPU and never load
the TPU's library: the harness is driven with ``on_chip=False``."""
from __future__ import annotations

import importlib.util
import io
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
_spec = importlib.util.spec_from_file_location(
    "chipbench_run", os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)
control_mod = run.load_module(os.path.join(BENCH, "control.py"))
devtrace = run.load_module(os.path.join(BENCH, "devtrace.py"))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = ("step_us_per_access", "entry_host_ms_per_replay",
           "device_idle_share")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# BENCHMARK.json: names, units, and files found by name
# ---------------------------------------------------------------------------

def test_benchmark_names_units_and_files_resolve():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in b["end_to_end"]}
    assert {"setup_s", "acc_per_s"} <= e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    for c in b["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        for k in c["reduced"]:
            assert NAME.match(k) and k in cfg["reduced"], k
    for w in b["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = run.load_cell(w["name"])     # every module found by name
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", "families", cell["mix"]["family"] + ".py"))
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_config_states_the_traffic_it_runs():
    """The YCSB keys of a configuration are the sizes its mix draws."""
    for w in _bench()["workloads"]:
        cell = run.load_cell(w["name"])
        cfg, mix = cell["config"], cell["mix"]
        assert mix["n_items"] == cfg["recordcount"]
        assert mix["length"] == cfg["operationcount"]
        assert mix["alpha"] == cfg["zipfian_constant"]
        assert cfg["kwargs"]["capacity"] == cfg["capacity"] == (
            cfg["cache_memory_mb"] << 20) // cfg["record_bytes"]


def test_added_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later cell comes as new files and entries only: a configuration,
    a mix of a new traffic family, and a per-layer metric."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = _bench()
    b["configs"].append({"name": "kv-16k", "source": "x", "reduced": [],
                         "file": "chipbench/configs/kv-16k.json", "why": "x"})
    b["workloads"].append({"name": "kv-16k.uniform", "config": "kv-16k",
                           "traffic": "uniform", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "replays_traced", "unit": "replays",
                           "better": "higher", "source": "device_trace",
                           "layer": "entry", "moves": "acc_per_s",
                           "workloads": ["kv-16k.uniform"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cfg = json.loads((tmp_path / "chipbench/configs/kv-64k.json")
                     .read_text())
    cfg["kwargs"]["capacity"] = 16384
    (tmp_path / "chipbench/configs/kv-16k.json").write_text(json.dumps(cfg))
    (tmp_path / "chipbench/traffic/families/uniform.py").write_text(
        "import numpy as np\n"
        "def trace(params, seed):\n"
        "    return np.random.default_rng(seed).integers(\n"
        "        0, params['n_items'], params['length'])\n")
    mix = {"family": "uniform", "pool": 2, "length": 64, "warmup": 0,
           "n_items": 1 << 20}
    (tmp_path / "chipbench/traffic/uniform.json").write_text(json.dumps(mix))
    (tmp_path / "chipbench/metrics/replays_traced.py").write_text(
        "def reduce(trace, record):\n    return len(trace['chips'])\n")
    cell = run.load_cell("kv-16k.uniform", str(tmp_path))
    assert cell["config"]["kwargs"]["capacity"] == 16384
    m, mod = cell["per_layer"][-1]
    assert m["name"] == "replays_traced" and mod.reduce({"chips": {1: 0}},
                                                        {}) == 1
    assert "replays_traced" not in [
        m["name"] for m, _ in run.load_cell("kv-64k.ycsbc",
                                            str(tmp_path))["per_layer"]]
    pool = cell["generate"].generate(cell["mix"], 7,
                                     str(tmp_path / "chipbench/traffic"))
    assert len(pool) == 2 and pool[0].shape == (64,)
    assert not np.array_equal(pool[0], pool[1])


# ---------------------------------------------------------------------------
# traffic: deterministic in the seed, equal to the repository's generator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["ycsbc"])
def test_traffic_is_deterministic_and_matches_repo_generator(mix):
    from repro.traces import synthetic
    gen = run.load_module(os.path.join(BENCH, "traffic", "generate.py"))
    p = dict(gen.load(mix), pool=3, length=512)
    seed = 3_000_000_017
    a, b = gen.generate(p, seed), gen.generate(p, seed)
    c = gen.generate(p, seed + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1])
    want = synthetic.zipf_trace(512, n_items=p["n_items"], alpha=p["alpha"],
                                seed=[seed, 2])
    assert a[2].shape == (512,)
    np.testing.assert_array_equal(a[2], want)


# ---------------------------------------------------------------------------
# the harness refuses to run without a TPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bare", [False, True])
def test_harness_exits_nonzero_without_tpu(tmp_path, bare):
    root = ROOT
    if bare:           # only BENCHMARK.json and the benchmark's own files
        root = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        shutil.copytree(BENCH, os.path.join(root, "chipbench"),
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "kv-64k.ycsbc",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr


# ---------------------------------------------------------------------------
# metric reductions on hand-built traces
# ---------------------------------------------------------------------------

REC = {"accesses_per_replay": 4}
CHIP = "/device:TPU:0"


def _metric(name, trace, rec=REC):
    return run.load_module(os.path.join(BENCH, "metrics", name + ".py")
                           ).reduce(trace, rec)


def _sessions(head, mid, tail):
    """Sessions of one chip from ``{line: [(name, start, dur)]}``."""
    return {tag: [(CHIP, list(lines.items()))]
            for tag, lines in (("head", head), ("mid", mid), ("tail", tail))
            if lines is not None}


HEAD = {"XLA Modules": [("jit_init(1)", 2, 3)],
        "XLA Ops": [("%init = s32[8]", 2, 3), ("%scan.1 = s32[1]", 10, 2),
                    ("%scan.2 = s32[1]", 13, 2)]}
MID = {"XLA Ops": [("%scan.1 = s32[1]", 40, 1), ("%scan.2 = s32[1]", 42, 2)]}
TAIL = {"XLA Modules": [("jit_readback(2)", 90, 2)],
        "XLA Ops": [("%scan.1 = s32[1]", 80, 2), ("%scan.2 = s32[1]", 84, 4),
                    ("%copy = s32[8]", 90, 2)]}


def test_readers_return_nothing_without_device_events():
    trace = devtrace.condense({"head": [("/host:CPU", [])]}, [0, 100],
                              "jit_step_ref")
    for metric in METRICS:
        assert _metric(metric, trace) is None


def test_condense_whole_step_program_and_idle():
    """The step program is placed from its first op in the head to its last
    in the tail; the middle sample's gaps between ops count as idle."""
    trace = devtrace.condense(_sessions(HEAD, MID, TAIL), [0, 100],
                              "jit_step_ref")
    chip = trace["chips"][CHIP]
    assert chip["step"] == [10, 88]
    assert [m[0] for m in chip["modules"]] == ["jit_init(1)",
                                               "jit_readback(2)"]
    assert chip["busy"] == [[2, 5], [10, 88], [90, 92]]
    assert chip["mid"]["span"] == [40, 44] and chip["mid"]["busy_ns"] == 3
    assert _metric("step_us_per_access", trace) == pytest.approx(78 / 1e3 / 4)
    assert _metric("entry_host_ms_per_replay", trace) == \
        pytest.approx(17 / 1e6)
    # busy 83 ns of 100, of which a quarter of the step's 78 is op gaps
    assert _metric("device_idle_share", trace) == pytest.approx(36.5)
    busy, breakdown = devtrace.summary(trace)
    assert busy["window_s"] == pytest.approx(100 / 1e9)
    assert busy["busy_s"] == pytest.approx(63.5 / 1e9)
    assert breakdown["device_ops"][0] == [
        "step program (placed by its first and last ops)",
        pytest.approx(78 / 1e9)]
    gaps = {}
    for w, g in breakdown["idle_gaps"]:
        gaps[w] = gaps.get(w, 0) + g
    assert gaps["before the step program: staging, set-up programs, "
                "dispatch"] == pytest.approx(7 / 1e9)    # [0,2] and [5,10]
    assert gaps["after the step program: readback"] == pytest.approx(10 / 1e9)
    assert sum(gaps.values()) == pytest.approx(36.5 / 1e9)


def test_condense_step_program_across_sessions():
    """A step module event that a session clips where it closed does not
    shorten the step: the tail's last op still ends it."""
    head = dict(HEAD, **{"XLA Modules": HEAD["XLA Modules"]
                         + [("jit_step_ref(9)", 10, 20)]})
    trace = devtrace.condense(_sessions(head, MID, TAIL), [0, 100],
                              "jit_step_ref")
    assert trace["chips"][CHIP]["step"] == [10, 88]


@pytest.mark.parametrize("missing", ["head", "tail"])
def test_step_program_not_placed_without_head_or_tail(missing):
    parts = {"head": HEAD, "mid": MID, "tail": TAIL, missing: None}
    trace = devtrace.condense(_sessions(parts["head"], parts["mid"],
                                        parts["tail"]), [0, 100],
                              "jit_step_ref")
    assert trace["chips"][CHIP]["step"] is None
    for metric in METRICS:
        assert _metric(metric, trace) is None


# ---------------------------------------------------------------------------
# whole runs on the CPU at a small size: sound, control, planted faults
# ---------------------------------------------------------------------------

SMALL = ("kv-64k", "ycsbc", {"capacity": 64},
         {"pool": 2, "length": 1500, "warmup": 300})


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A checkout whose cell keeps the real files' shape at a small size."""
    root = tmp_path_factory.mktemp("chipbench_small")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = _bench()
    config, mix, kw, mp = SMALL
    cfg = json.loads((root / f"chipbench/configs/{config}.json").read_text())
    cfg["kwargs"].update(kw)
    (root / "chipbench/configs/small-kv.json").write_text(json.dumps(cfg))
    p = json.loads((root / f"chipbench/traffic/{mix}.json").read_text())
    p.update(mp)
    (root / "chipbench/traffic/small-kv.json").write_text(json.dumps(p))
    b["workloads"].append({"name": "small-kv.t", "config": "small-kv",
                           "traffic": "small-kv", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)


def _run(root, cell, program=None, entry=None, seed=4_000_000_001, trace=0):
    out, err = io.StringIO(), io.StringIO()
    res = run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.01", "--trace", str(trace)], root=root,
                  on_chip=False, program=program, entry=entry, out=out,
                  err=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line == json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith(
        f"[chipbench {cell}] check ")
    return line


@pytest.mark.parametrize("cell,seed", [("small-kv.t", 4_000_000_001),
                                       ("small-kv.t", 2**31 + 12_345)],
                         ids=["small-kv.t", "small-kv.t-seed-over-2-31"])
def test_run_on_cpu_is_correct(small_root, cell, seed):
    line = _run(small_root, cell, seed=seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"acc_per_s", "setup_s"}
    assert all(v["value"] == 0 for v in line["checks"].values())


def test_traced_run_fails_where_the_step_program_is_not_placed(small_root):
    """On the CPU the sessions hold no TPU plane: the run must not pass off
    missing per-layer metrics as a result."""
    with pytest.raises(RuntimeError, match="not placed"):
        run.run(["--workload", "small-kv.t", "--seed", "5", "--seconds",
                 "0.01", "--trace", "1"], root=small_root, on_chip=False,
                out=io.StringIO(), err=io.StringIO())


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("name", ["kv"])
def test_reference_in_the_programs_place_and_its_control(small_root, name,
                                                         control):
    """The reference itself passes; the control (no §3.3 aging) fails."""
    cell = f"small-{name}.t"
    entry = control_mod.reference_entry(run.load_cell(cell, small_root),
                                        control)
    line = _run(small_root, cell, entry=entry)
    assert line["correct"] is (not control)
    if control:
        assert line["checks"]["hits_differing"]["value"] > 0
        assert line["checks"]["sketch_words_differing"]["value"] > 0


def _broken(fault):
    """``simulate_trace`` with one fault planted where its answer is made."""
    from repro.core.device_simulate import simulate_trace

    def program(keys, **kw):
        res, state, hits = simulate_trace(keys, **kw)
        hits = np.array(hits)
        state = {k: np.array(v) for k, v in state.items()}
        if fault == "hit_altered":
            hits[len(hits) // 2] ^= 1
        elif fault == "state_unchanged":
            for k in ("counters", "doorkeeper"):
                state[k] = np.zeros_like(state[k])
        elif fault == "half_trace_left_out":
            half = len(keys) // 2
            res, state, h2 = simulate_trace(keys[:half], **kw)
            hits[:half], hits[half:] = np.asarray(h2), 0
        return res, state, hits
    return program


@pytest.mark.parametrize("cell,fault", [
    ("small-kv.t", "hit_altered"), ("small-kv.t", "state_unchanged"),
    ("small-kv.t", "half_trace_left_out")])
def test_planted_fault_makes_run_incorrect(small_root, cell, fault):
    line = _run(small_root, cell, program=_broken(fault))
    assert line["correct"] is False and line["failed"] >= 1
