"""Pool-ownership AST linter over ``src/`` (the fifth shipped pass).

The page pool is the single source of truth for KV bytes (PR 5); this
linter enforces the discipline around it at the source level, where jaxpr
passes cannot see:

  deny-list      : names that must never reappear in ``src/`` — APIs whose
                   existence implies a dense per-slot KV mirror.  Replaces
                   (and generalizes) the ``refresh_pool_from_slots`` grep
                   pin that lived in tests/test_pool_native.py.
  alloc-release  : every module that takes page references must also give
                   them back — a module calling ``allocate``/``acquire``
                   without ``release``/``drop_cached``, ``retain`` without
                   ``drop_cached``, or ``paged_pin_pages`` without
                   ``paged_release_pages`` leaks pool pages by construction
                   (the shutdown orphan sweep would catch it dynamically;
                   this catches it at review time).
  tick-host-pull : the serving engine's per-tick methods are flagged for
                   host pulls (``np.asarray``/``np.array``/
                   ``.block_until_ready``/``jax.device_get``) — each is a
                   device sync on the token clock.  Legitimate sites (the
                   emitted-token pull, interval-amortized planning reads)
                   are waived in the committed baseline, so NEW pulls fail
                   loudly.
"""

from __future__ import annotations

import ast
from pathlib import Path

from repro.analysis.report import Violation

# APIs banned from src/: name -> why.
DENY_APIS = {
    "refresh_pool_from_slots":
        "re-derives the pool from a dense per-slot KV master — that master "
        "was retired in the pool-ownership inversion (PR 5); the pool IS "
        "the source of truth",
    "refresh_pool_from_cache":
        "same dense-mirror pattern under another name",
}

# (needs, satisfied-by): module-level reference-pairing rules.
PAIR_RULES = (
    (("allocate", "acquire"), ("release", "drop_cached"),
     "takes page refs but never releases"),
    (("retain",), ("drop_cached",),
     "retains cached pages but never drops"),
    (("paged_pin_pages",), ("paged_release_pages",),
     "pins pages into the near tier but never releases their tier state"),
)

# Per-tick methods, by class: these run on the decode token clock.
# Boundary methods (_admit/_retire, __init__, shutdown sweeps) are
# deliberately NOT listed — they run per request, not per token.
# ``_host`` is the engine's one counted, traced read helper: the per-tick
# methods pull through it, never directly.
TICK_METHODS = {
    "ServingEngine": ("run", "_serve", "_admit_arrivals", "_decode_tick",
                      "_maintain", "_flush_mapping", "_pin_static",
                      "_far_rows_shadow", "_account_kv_bytes", "_host"),
}

# Host-pull callees flagged inside tick methods.
HOST_PULL_CALLS = ("np.asarray", "np.array", "jax.device_get",
                   ".block_until_ready")


def _callee_name(func: ast.AST) -> str:
    """Dotted name of a call target, best effort ('np.asarray',
    '.block_until_ready' for method calls on expressions)."""
    parts = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return "." + ".".join(reversed(parts)) if parts else ""


def _names_referenced(tree: ast.AST):
    """Every identifier a module mentions: names, attributes, defs,
    imports — the surface the deny-list matches against."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            yield node.id, node
        elif isinstance(node, ast.Attribute):
            yield node.attr, node
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            yield node.name, node
        elif isinstance(node, ast.alias):
            yield (node.asname or node.name).split(".")[-1], node


def _called_names(tree: ast.AST) -> set[str]:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _callee_name(node.func)
            if name:
                out.add(name.split(".")[-1])
    return out


def _tick_method_pulls(tree: ast.AST):
    """(class.method, callee, lineno) for host pulls in tick methods."""
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef) or cls.name not in TICK_METHODS:
            continue
        ticks = TICK_METHODS[cls.name]
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    or fn.name not in ticks:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _callee_name(node.func)
                for pull in HOST_PULL_CALLS:
                    if callee == pull or (pull.startswith(".")
                                          and callee.endswith(pull)):
                        yield (f"{cls.name}.{fn.name}", pull, node.lineno)


def lint_ownership(root: str | Path) -> list[Violation]:
    """Run the three ownership rules over every ``*.py`` under ``root``."""
    root = Path(root)
    viols: list[Violation] = []
    for path in sorted(root.rglob("*.py")):
        if "analysis" in path.parts:
            continue          # the linter's own deny-list strings
        rel = str(path.relative_to(root.parent.parent)
                  if root.parent.parent in path.parents else path)
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError as e:
            viols.append(Violation(
                pass_name="pool-ownership", rule="syntax-error", where=rel,
                detail=f"unparseable module: {e.msg}"))
            continue

        seen_deny = set()
        for name, node in _names_referenced(tree):
            if name in DENY_APIS and name not in seen_deny:
                seen_deny.add(name)
                viols.append(Violation(
                    pass_name="pool-ownership", rule="deny-list",
                    where=rel, detail=f"`{name}` is banned: "
                                      f"{DENY_APIS[name]}",
                    source=f"{rel}:{getattr(node, 'lineno', 0)}"))

        called = _called_names(tree)
        for needs, satisfies, why in PAIR_RULES:
            hit = sorted(set(needs) & called)
            if hit and not (set(satisfies) & called):
                viols.append(Violation(
                    pass_name="pool-ownership", rule="unpaired-ref",
                    where=rel,
                    detail=f"calls {hit} but none of {list(satisfies)}: "
                           f"{why}"))

        seen_pulls = set()
        for qual, pull, lineno in _tick_method_pulls(tree):
            key = (qual, pull)
            if key in seen_pulls:
                continue
            seen_pulls.add(key)
            viols.append(Violation(
                pass_name="pool-ownership", rule="tick-host-pull",
                where=f"{rel}::{qual}",
                detail=f"host pull via {pull}",
                source=f"{rel}:{lineno}"))
    return viols
