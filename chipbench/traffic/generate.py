"""The one generator every traffic mix of the benchmark goes through.

A mix is a data file beside this one (``<mix>.json``).  Its ``family``
names a module of ``families/`` (``families/<family>.py``), whose
``trace(params, seed)`` draws one trace from the mix's other keys; a new
family is a new file there, found by name.  From the run's ``--seed`` the
generator draws a pool of ``pool`` traces of one shape, trace ``i`` from
the seed pair ``(seed, i)``, so that every seed gives the same sizes, and
other keys.
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, root: str = HERE) -> dict:
    """The parameters of mix ``name`` (``<root>/<name>.json``)."""
    with open(os.path.join(root, name + ".json")) as f:
        return json.load(f)


def family(name: str, root: str = HERE):
    """The module ``<root>/families/<name>.py``."""
    path = os.path.join(root, "families", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_family_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(params: dict, seed: int, root: str = HERE) -> list[np.ndarray]:
    """The run's pool of traces, all of one shape."""
    draw = family(params["family"], root).trace
    return [draw(params, [int(seed), i]) for i in range(params["pool"])]
