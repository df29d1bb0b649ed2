"""host-sync — no silent device round-trips where they stall the pipeline.

Two hot-context kinds are scanned:

* **jit regions** — functions decorated with ``jax.jit`` /
  ``functools.partial(jax.jit, ...)``, lambdas passed to ``jax.jit``, and
  local defs wrapped via ``jax.jit(fn, ...)``.  Here ``.item()``,
  ``np.asarray`` / ``np.array``, ``jax.device_get``,
  ``block_until_ready`` and ``float()``/``int()`` over non-static values
  are all flagged: under trace they either raise
  (``ConcretizationTypeError``) at an unhelpful distance or silently
  constant-fold a value that should be traced.
* **hot-path functions** (:attr:`LintConfig.hot_paths` — the engine's
  decode step loop and the plan's run/staging paths).  These run host
  Python between device dispatches, so a stray sync serializes the
  pipeline; the same calls are flagged.  Deliberate syncs (the plan's
  residency trace points, the engine's per-step sampling read) carry
  ``# replint: disable=host-sync`` pragmas with their one-line why.

``float()``/``int()`` over shape/ndim/size/len expressions or literals
are static and exempt.
"""
from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set

from repro.analysis.lint.findings import (Finding, ModuleInfo, Rule,
                                          call_name, dotted, parent_map,
                                          symbol_of)

_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready",
               "np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_CASTS = {"float", "int"}


def _jit_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = call_name(node)
    if name == "jax.jit":
        return True
    return name in ("functools.partial", "partial") and bool(node.args) \
        and dotted(node.args[0]) == "jax.jit"


def _static_cast(call: ast.Call) -> bool:
    """float()/int() over literals or shape arithmetic is trace-static."""
    if not call.args:
        return True
    arg = call.args[0]
    if isinstance(arg, ast.Constant):
        return True
    for sub in ast.walk(arg):
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim",
                                                           "size", "dtype"):
            return True
        if isinstance(sub, ast.Call) and call_name(sub) == "len":
            return True
    return False


def _jit_regions(tree: ast.Module) -> List[ast.AST]:
    """Function/lambda nodes whose bodies trace under jax.jit."""
    defs_by_name = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, node)
    regions: List[ast.AST] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and any(_jit_call(d) for d in node.decorator_list):
            regions.append(node)
        elif isinstance(node, ast.Call) and call_name(node) == "jax.jit" \
                and node.args:
            target = node.args[0]
            if isinstance(target, ast.Lambda):
                regions.append(target)
            elif isinstance(target, ast.Name) \
                    and target.id in defs_by_name:
                regions.append(defs_by_name[target.id])
    return regions


class HostSyncRule(Rule):
    name = "host-sync"
    description = ("no .item()/np.asarray/device_get/block_until_ready/"
                   "float()/int() syncs inside jit regions or hot-path "
                   "functions")

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        parents = parent_map(mod.tree)
        hot: List[tuple] = []          # (node, context-label)
        for region in _jit_regions(mod.tree):
            hot.append((region, "jit region"))
        hot_paths: Set[tuple] = set(mod.config.hot_paths)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef):
                for m in node.body:
                    if isinstance(m, ast.FunctionDef) \
                            and (node.name, m.name) in hot_paths:
                        hot.append((m, "hot path"))
        seen: Set[int] = set()
        for region, label in hot:
            for f in self._scan(mod, region, label, parents):
                key = hash((f.line, f.col, f.message))
                if key not in seen:
                    seen.add(key)
                    yield f

    def _scan(self, mod: ModuleInfo, region: ast.AST, label: str,
              parents) -> Iterator[Finding]:
        for node in ast.walk(region):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            what: Optional[str] = None
            if name in _SYNC_CALLS:
                what = f"'{name}'"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("item", "block_until_ready"):
                what = f"'.{node.func.attr}()'"
            elif name in _CASTS and not _static_cast(node):
                what = f"'{name}()' over a device value"
            if what is not None:
                yield Finding(
                    self.name, mod.path, node.lineno, node.col_offset,
                    f"{what} forces a host sync inside a {label} — hoist "
                    f"it out of the hot path or suppress with a "
                    f"justification if the sync is the design",
                    symbol_of(node, parents))
