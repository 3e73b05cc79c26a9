#!/usr/bin/env python3
"""The benchmark's control: the plain reference put in the program's place.

    python3 chipbench/control.py --workload <cell> --seed <n> [--seconds <s>] [--sound]

Drives a whole run of the cell, set-up, window and check, with the entry
replaced by the configuration's reference with one stated guarantee
broken (``answer(..., control=True)``: the §3.3 aging left out).  Its run
must come out ``correct: false``; the numbers it compares are the upper
readings that the limits in the configuration files sit below.
``--sound`` puts the unbroken reference in the program's place instead,
which must pass.  Exits 0 when the run comes out as it must.

It runs on the host alone (JAX on the CPU, no chip held), at the cell's
own sizes; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))


def _harness():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(HERE, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_entry(cell: dict, control: bool):
    """An entry module whose calls the cell's reference answers (or its
    control), in the form the real entry's ``readback`` gives."""
    ref, kwargs, mix = cell["reference"], cell["config"]["kwargs"], cell["mix"]

    def make(kwargs_, mix_, program=None):
        return lambda keys: ref.answer(kwargs, mix, keys, control=control)
    return SimpleNamespace(make=make, units=cell["entry"].units,
                           readback=lambda got: got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "cpu"
    run = _harness()
    root = os.path.dirname(HERE)
    entry = reference_entry(run.load_cell(args.workload, root),
                            not args.sound)
    res = run.run(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "0"],
                  root=root, on_chip=False, entry=entry)
    return 0 if res["correct"] is args.sound else 1


if __name__ == "__main__":
    sys.exit(main())
