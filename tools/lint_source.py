#!/usr/bin/env python
"""Python-AST lint for the kernel source: the write-discipline at the
source level, complementing the compiled-program lint
(``tools/lint_programs.py``) which checks what XLA actually emitted.

Rules:

``S1``  raw ``.at[...]`` indexed-update chains in ``src/repro/kernels/``
        are banned outside the approved write helpers (``_lset*``) and
        the epoch-boundary / init / host-reference scopes listed in
        ``ALLOWED_AT_SCOPES``.  Per-access writes must go through the
        helpers — they are what keeps lane batching scatter-free and the
        single-word DUS discipline honest (lint rule R1's source-level
        twin).
``S2``  computed-index subscript loads (``tab[h % N]``-style inline
        gathers, ``jnp.take``) in ``src/repro/kernels/`` outside the
        approved gather helpers: reads of dynamic positions must go
        through ``_ds_gather`` / reviewed helper scopes so the
        ``_big_operand`` width-cliff discipline applies (R-series
        symptom: the 2^18 gather-partitioning cliff).
``S3``  module-level memo dicts (``_x_cache = {}``) anywhere in
        ``src/repro/`` must be bounded: the file must apply the
        clear-on-full pattern (``if len(cache) >= LIMIT: cache.clear()``)
        — the ``_mesh_cache``/``_vmap_cache``/``_pallas_cache`` leak
        class fixed reactively in PRs 6 and 8, now enforced statically.

Exit codes: 0 clean, 1 findings.
"""
import argparse
import ast
import sys
from pathlib import Path

# S1: functions whose whole body may use raw .at[] updates.
#   - the approved write helpers themselves (their implementation IS the
#     discipline: off-lane they emit the plain .at[].set)
#   - epoch-boundary scopes (rebalance/merge run once per epoch, not per
#     access; their gather/scatter cost is amortized by design)
#   - init-time and pallas-kernel scopes (not part of the traced scan)
ALLOWED_AT_SCOPES = {
    "_lset", "_lset_row", "_lset_col",            # the write helpers
    "_rebalance_flat", "_rebalance_set",          # epoch boundary
    "compact",                                    # epoch boundary
    "init_step_state",                            # init time
    "_step_kernel",                               # pallas body (Ref ops)
}
# S1/S2: whole files outside the fused-scan discipline: the O(capacity)
# host-reference kernel, the epoch-boundary merge fold, and the pallas
# serving kernels (Ref indexing, not traced gathers)
ALLOWED_FILES = {"ref.py", "sketch_merge.py", "sketch_update.py",
                 "sketch_estimate.py"}

# S2: scopes that may read computed indices directly — each one either
# implements the width-cliff discipline or carries the _big_operand
# guard internally (the small-width fused-gather branch is the approved
# fast path there)
ALLOWED_GATHER_SCOPES = {
    "_ds_gather",                                  # the gather helper
    "_estimate_pair", "_estimate_block",           # _big_operand-guarded
    "_one_access_set_arc",                         # _big_operand-guarded
    "bit_get",                                     # packed-bitset helper
    "probe_index", "dk_probe_index",               # python const tables
    "set_table",                                   # init-time numpy
} | ALLOWED_AT_SCOPES


def _enclosing_functions(tree):
    """Map every node -> tuple of enclosing function names, outermost
    first (an inner ``body`` closure inherits its parent's approval)."""
    owner = {}

    def walk(node, chain):
        for child in ast.iter_child_nodes(node):
            nchain = chain
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nchain = chain + (child.name,)
            owner[child] = nchain
            walk(child, nchain)
    walk(tree, ())
    return owner


def _is_at_chain(node: ast.Subscript) -> bool:
    """``<expr>.at[...]`` — the jax indexed-update property."""
    return (isinstance(node.value, ast.Attribute)
            and node.value.attr == "at")


def _computed_index(node: ast.expr) -> bool:
    """An index expression with arithmetic or calls in it — the inline
    hash-derived gather S2 bans.  Plain names/constants/slices pass (a
    static type can't tell a python int from a traced array, so a
    deliberate variable assignment is the reviewable unit)."""
    if isinstance(node, ast.Tuple):
        return any(_computed_index(e) for e in node.elts)
    if isinstance(node, ast.Slice):
        return False
    return any(isinstance(n, (ast.BinOp, ast.Call))
               for n in ast.walk(node))


def lint_kernels_file(path: Path) -> list:
    findings = []
    tree = ast.parse(path.read_text())
    owner = _enclosing_functions(tree)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Subscript):
            continue
        chain = owner.get(node, ())
        label = chain[-1] if chain else "<module>"
        if _is_at_chain(node):
            if not any(fn in ALLOWED_AT_SCOPES for fn in chain):
                findings.append(
                    ("S1", path, node.lineno,
                     f"raw .at[] update in {label}() — use the "
                     "_lset*/_ldus* write helpers (or add the scope "
                     "to ALLOWED_AT_SCOPES with a reason)"))
        elif isinstance(node.ctx, ast.Load) and \
                _computed_index(node.slice):
            if not any(fn in ALLOWED_GATHER_SCOPES for fn in chain):
                findings.append(
                    ("S2", path, node.lineno,
                     f"computed-index gather in {label}() — read "
                     "through _ds_gather (width-cliff discipline) "
                     "or an approved helper scope"))
    return findings


def lint_memo_dicts(path: Path) -> list:
    """S3: every module-level ``NAME = {}`` must be bounded in-file."""
    findings = []
    src = path.read_text()
    tree = ast.parse(src)
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            value, targets = node.value, node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            value, targets = node.value, [node.target]
        else:
            continue
        if not (isinstance(value, ast.Dict) and not value.keys):
            continue
        for t in targets:
            if not isinstance(t, ast.Name):
                continue
            name = t.id
            if f"len({name})" not in src and f"{name}.clear()" not in src:
                findings.append(
                    ("S3", path, node.lineno,
                     f"module-level memo dict {name!r} has no bound — "
                     "apply the clear-on-full pattern "
                     f"(if len({name}) >= LIMIT: {name}.clear())"))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="AST lint: raw .at[] / inline gathers / unbounded "
                    "memo dicts")
    ap.add_argument("--root", default=str(
        Path(__file__).resolve().parents[1]))
    args = ap.parse_args(argv)
    root = Path(args.root)

    findings = []
    for path in sorted((root / "src" / "repro" / "kernels").glob("*.py")):
        if path.name in ALLOWED_FILES:
            continue
        findings += lint_kernels_file(path)
    for path in sorted((root / "src" / "repro").rglob("*.py")):
        findings += lint_memo_dicts(path)

    for rule, path, line, msg in findings:
        print(f"FAIL [{rule}] {path.relative_to(root)}:{line}: {msg}")
    if findings:
        print(f"{len(findings)} finding(s)")
        return 1
    print("source lint clean (S1-S3)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
