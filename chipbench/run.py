#!/usr/bin/env python3
"""Chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness finds everything else by name:

    chipbench/configs/<config>.json    the deployment: its entry, the entry's
                                       keyword arguments, its reference
                                       module and the compared numbers' limits
    chipbench/configs/<reference>.py   plain reference: compare(), answer()
    chipbench/entries/<entry>.py       how a run calls the program:
                                       make(), units(), readback()
    chipbench/traffic/<mix>.json       parameters of traffic/generate.py
    chipbench/metrics/<metric>.py      reduce(trace, record) -> value | None

Set-up (``setup_s``, from process start): the compile cache, the TPU, a
seeded pool of traces, one warm replay at the cell's shape (two in a
traced run, the second timed).  The window then replays the pool round
robin, one call of the entry per replay, each from a cold cache, until
``--seconds`` have passed; the replay that straddles the deadline is
finished and counted.  ``acc_per_s`` is every access of the window's
replays over the window's wall time.  Compiles inside the window are
counted and printed.

With ``--trace 1`` three profiler sessions sample the window's first
replay (``devtrace.py``) and the per-layer metrics are reduced from them.
After the window the harness reads the device's peak memory, reads back a
seed-drawn sample of the window's replays, frees the rest, and compares
the sample with the configuration's plain reference.  Each compared number
is printed with its limit, last on standard error and last in the result
line.

The result is the last line of standard output, one JSON object.  With no
TPU, or fewer chips than the cell needs, the run exits 1 and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + os.path.splitext(os.path.basename(path))[0]
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, mix, modules and metric
    entries, each found by the name ``BENCHMARK.json`` gives it."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}: {sorted(cells)}")
    cell = cells[name]
    here = os.path.join(root, "chipbench")
    config = _json(os.path.join(here, "configs", cell["config"] + ".json"))
    gen = load_module(os.path.join(here, "traffic", "generate.py"))

    def ours(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": config, "generate": gen,
            "mix": gen.load(cell["traffic"], os.path.join(here, "traffic")),
            "entry": load_module(os.path.join(here, "entries",
                                              config["entry"] + ".py")),
            "reference": load_module(os.path.join(
                here, "configs", config["reference"] + ".py")),
            "end_to_end": [m for m in bench["end_to_end"] if ours(m)],
            "per_layer": [(m, load_module(os.path.join(
                here, "metrics", m["name"] + ".py")))
                for m in bench["per_layer"] if ours(m)]}


def run(argv=None, root: str = ROOT, on_chip: bool = True, program=None,
        entry=None, out=sys.stdout, err=sys.stderr) -> dict:
    """One run of one cell; returns the result line's object.

    ``on_chip=False`` (no look for a TPU, no persistent compile cache),
    ``program=`` (stands in for the program the entry calls) and
    ``entry=`` (stands in for the entry module) serve the harness's own
    tests and its control, which drive a run on the CPU.
    """
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = load_cell(args.workload, root)
    cell, cfg, mix = c["cell"], c["config"], c["mix"]
    entry = entry or c["entry"]

    def log(*a):
        print(f"[chipbench {cell['name']}]", *a, file=err, flush=True)

    if on_chip:         # the TPU runtime's logs stay inside the checkout
        os.environ.setdefault("TPU_LOG_DIR",
                              os.path.join(root, ".chipbench_trace", "logs"))
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    if on_chip:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(root, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if on_chip and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise NoChip(f"cell {cell['name']} needs {cell['chips']} TPU chip(s);"
                     f" JAX found {len(devices)} {dev.platform} device(s)")
    log(f"device {dev.platform} {dev.device_kind!r} x{len(devices)}")
    compiles = [0]

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    pool = c["generate"].generate(mix, args.seed,
                                  os.path.join(root, "chipbench", "traffic"))
    call = entry.make(cfg["kwargs"], mix, program)
    for _ in range(1 + args.trace):   # traced: time a replay that is warm
        t_warm = time.perf_counter()
        call(pool[-1])
    t1 = time.perf_counter()
    warm_s, setup_s = t1 - t_warm, t1 - T_START
    log(f"setup {setup_s!r} s (last warm replay {warm_s!r} s), "
        f"compiles in set-up {compiles[0]}")

    devtrace = load_module(os.path.join(root, "chipbench", "devtrace.py"))
    trace_dir = os.path.join(root, ".chipbench_trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    files, outs, c0 = {}, [], compiles[0]
    t0 = time.perf_counter()
    while True:
        keys = pool[len(outs) % len(pool)]
        if args.trace and not outs:
            sessions = devtrace.Sessions(jax, trace_dir, warm_s)
            s0 = time.time_ns()
            outs.append((keys, call(keys)))
            span = [s0, time.time_ns()]
            files = sessions.close()
        else:
            outs.append((keys, call(keys)))
        t1 = time.perf_counter()
        if t1 - t0 >= args.seconds:
            break
    elapsed = t1 - t0
    in_window = compiles[0] - c0
    units = sum(entry.units(k) for k, _ in outs)
    log(f"window {elapsed!r} s: {len(outs)} replays, {units} accesses, "
        f"compiles in window {in_window}")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    # a seed-drawn sample of the window's replays, read back; the rest freed
    rng = np.random.default_rng([args.seed, len(outs)])
    pick = sorted(rng.choice(len(outs), min(len(outs), cfg["check_replays"]),
                             replace=False).tolist())
    checked = [(outs[i][0], entry.readback(outs[i][1])) for i in pick]
    n_replays = len(outs)
    del outs

    result = {"correct": None, "attempted": None, "failed": None,
              "metrics": {}, "device": device}
    if args.trace:
        trace = devtrace.condense(devtrace.read_sessions(files), span,
                                  cfg["step_program"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: sessions {sorted(files)}; " + "; ".join(
            f"{p}: step program {ch['step']}, {len(ch['modules'])} other "
            f"programs, middle sample {ch['mid'] and ch['mid']['span']}"
            for p, ch in trace["chips"].items()))
        if not trace["chips"] or not all(ch["step"] for ch in
                                         trace["chips"].values()):
            raise RuntimeError(
                "traced run: the step program was not placed (its first op "
                "in the head session and its last in the tail); no "
                "per-layer metric can be read")
        busy, breakdown = devtrace.summary(trace)
        device.update(busy)
        result["breakdown"] = breakdown
        record = {"accesses_per_replay": entry.units(pool[0])}
        for m, mod in c["per_layer"]:
            v = mod.reduce(trace, record)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"acc_per_s": units / elapsed, "setup_s": setup_s}
        for m in c["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    ref, limits = c["reference"], cfg["limits"]
    t_ref = time.perf_counter()
    diffs, fill = [], {}
    for keys, got in checked:
        num, counts = ref.compare(cfg["kwargs"], mix, keys, got)
        diffs.append(num)
        for k, v in counts.items():
            fill[k] = fill.get(k, 0) + v
    log(f"reference over replays {pick} took "
        f"{time.perf_counter() - t_ref!r} s; table fill {json.dumps(fill)}")
    checks = {k: {"value": sum(d[k] for d in diffs), "limit": v}
              for k, v in limits.items()}
    failed = sum(any(d[k] > v for k, v in limits.items()) for d in diffs)
    result.update(correct=failed == 0, attempted=n_replays, failed=failed)
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    try:
        run(argv)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
