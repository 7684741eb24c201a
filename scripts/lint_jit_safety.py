#!/usr/bin/env python
"""Static jit-safety lint over ``pipegoose_tpu/`` (CI gate).

A host sync inside a jit-path module is the classic silent TPU
performance bug: ``.item()``, ``np.asarray``, ``jax.device_get`` or a
wall-clock read forces a device round-trip per call (or, under
``jit``, a tracer error at the worst possible time), and
nondeterministic host state (``datetime.now``, ``random.*``) bakes a
different program into every trace. This lint walks the library's AST
— no imports, no jax — and flags:

- ``host-sync``: ``.item()`` calls, ``np``/``numpy`` ``asarray``,
  ``jax.device_get``, and ``time.*`` calls, in modules NOT declared
  host-side;
- ``nondeterminism``: ``datetime.now/utcnow/today`` and ``random.*``
  module calls, in modules NOT declared host-side;
- ``bare-except``: ``except:`` with no exception class, in EVERY
  module (it swallows KeyboardInterrupt and tracer-leak errors alike).

The allowlist (``scripts/jit_safety_allowlist.txt``) names the KNOWN
host-side modules/functions — telemetry exporters, the serving host
scheduler, checkpoint I/O — one fnmatch pattern per line, either
``<path glob>`` (whole module) or ``<path glob>::<qualname glob>``
(one function/class). A line carrying a trailing ``# jit-host-ok``
comment in the source is also exempt (visible, reviewable waiver).

    python scripts/lint_jit_safety.py              # lint, exit 1 on findings
    python scripts/lint_jit_safety.py --verbose    # also list allowed hits

Tier-1 runs it on the tree (tests/test_lint_jit_safety.py).
"""
from __future__ import annotations

import argparse
import ast
import os
import sys
from fnmatch import fnmatch
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ROOT = "pipegoose_tpu"
DEFAULT_ALLOWLIST = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "jit_safety_allowlist.txt"
)

WAIVER = "jit-host-ok"

# module aliases numpy is commonly imported under; any attribute call
# of `time` counts as a host-clock read
_NP_NAMES = {"np", "numpy", "onp"}
_DATETIME_NONDET = {"now", "utcnow", "today"}


class Finding:
    def __init__(self, path: str, line: int, rule: str, message: str,
                 qualname: str):
        self.path, self.line, self.rule = path, line, rule
        self.message, self.qualname = message, qualname

    def key(self) -> Tuple[str, str]:
        return (self.path, self.qualname)

    def __str__(self) -> str:
        where = f" (in {self.qualname})" if self.qualname else ""
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}{where}"


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for an Attribute/Name chain, None for anything dynamic."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source_lines: List[str], host_side: bool):
        self.path = path
        self.lines = source_lines
        self.host_side = host_side
        self.stack: List[str] = []
        self.findings: List[Finding] = []

    # -- helpers -----------------------------------------------------------

    @property
    def qualname(self) -> str:
        return ".".join(self.stack)

    def _waived(self, node: ast.AST) -> bool:
        line = self.lines[node.lineno - 1] if node.lineno <= len(self.lines) \
            else ""
        return WAIVER in line

    def _add(self, node: ast.AST, rule: str, message: str) -> None:
        if not self._waived(node):
            self.findings.append(Finding(
                self.path, node.lineno, rule, message, self.qualname
            ))

    # -- scope tracking ----------------------------------------------------

    def visit_FunctionDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    # -- rules -------------------------------------------------------------

    def visit_ExceptHandler(self, node):
        if node.type is None:
            self._add(node, "bare-except",
                      "bare `except:` swallows KeyboardInterrupt and "
                      "tracer errors — name the exception class")
        self.generic_visit(node)

    def visit_Call(self, node):
        if not self.host_side:
            self._check_host_sync(node)
        self.generic_visit(node)

    def _check_host_sync(self, node: ast.Call) -> None:
        fn = node.func
        # x.item()
        if isinstance(fn, ast.Attribute) and fn.attr == "item" \
                and not node.args and not node.keywords:
            self._add(node, "host-sync",
                      "`.item()` forces a device->host sync per call")
            return
        dotted = _dotted(fn)
        if dotted is None:
            return
        head, _, tail = dotted.partition(".")
        if head in _NP_NAMES and tail in ("asarray", "array"):
            self._add(node, "host-sync",
                      f"`{dotted}` materializes device values on host "
                      f"(use jnp, or mark the module host-side)")
        elif dotted == "jax.device_get":
            self._add(node, "host-sync",
                      "`jax.device_get` is an explicit device->host fetch")
        elif head == "time" and tail and "." not in tail:
            self._add(node, "host-sync",
                      f"`{dotted}()` reads the host clock on the jit path "
                      f"(fence + measure outside, or mark host-side)")
        elif head == "random" and tail and "." not in tail:
            self._add(node, "nondeterminism",
                      f"`{dotted}()` draws unseeded host randomness — "
                      f"thread a jax PRNG key instead")
        elif tail.split(".")[-1] in _DATETIME_NONDET and "datetime" in dotted:
            self._add(node, "nondeterminism",
                      f"`{dotted}()` bakes wall-clock state into the "
                      f"traced program")


def load_allowlist(path: str) -> List[str]:
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for raw in f:
            line = raw.split("#", 1)[0].strip()
            if line:
                out.append(line)
    return out


def _allowed(patterns: List[str], relpath: str, qualname: str) -> bool:
    for pat in patterns:
        if "::" in pat:
            ppat, qpat = pat.split("::", 1)
            if fnmatch(relpath, ppat) and (
                fnmatch(qualname, qpat)
                or fnmatch(qualname, qpat + ".*")
            ):
                return True
        elif fnmatch(relpath, pat):
            return True
    return False


def lint_source(
    source: str, relpath: str, patterns: List[str]
) -> Tuple[List[Finding], List[Finding]]:
    """(violations, allowed) for one module's source text."""
    # whole-module status comes from module-form entries only — a
    # "path::*" qualname glob must not silently promote itself
    host_side = _allowed([p for p in patterns if "::" not in p],
                         relpath, "")
    tree = ast.parse(source, filename=relpath)
    linter = _Linter(relpath, source.splitlines(), host_side)
    linter.visit(tree)
    violations, allowed = [], []
    # a qualname glob of bare "*" is a whole-module entry in disguise —
    # it may clear host-sync findings but, like a real whole-module
    # entry, never a bare except
    qual_patterns = [
        p for p in patterns
        if "::" in p and p.split("::", 1)[1].strip() != "*"
    ]
    for f in linter.findings:
        if f.rule == "bare-except":
            # no module-level exemption — only a NAMED qualname entry
            # or an inline waiver clears a bare except
            ok = _allowed(qual_patterns, relpath, f.qualname)
        else:
            ok = host_side or _allowed(patterns, relpath, f.qualname)
        (allowed if ok else violations).append(f)
    return violations, allowed


def lint_tree(
    root: str, patterns: List[str], repo: str = REPO
) -> Tuple[List[Finding], List[Finding]]:
    violations: List[Finding] = []
    allowed: List[Finding] = []
    top = os.path.join(repo, root)
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            full = os.path.join(dirpath, name)
            rel = os.path.relpath(full, repo).replace(os.sep, "/")
            with open(full, encoding="utf-8") as f:
                src = f.read()
            v, a = lint_source(src, rel, patterns)
            violations += v
            allowed += a
    return violations, allowed


def main() -> int:
    ap = argparse.ArgumentParser(description="jit-safety static lint")
    ap.add_argument("--root", default=DEFAULT_ROOT,
                    help="directory to lint, relative to the repo root")
    ap.add_argument("--allowlist", default=DEFAULT_ALLOWLIST,
                    help="host-side allowlist file")
    ap.add_argument("--verbose", action="store_true",
                    help="also print allowlisted hits")
    args = ap.parse_args()

    patterns = load_allowlist(args.allowlist)
    violations, allowed = lint_tree(args.root, patterns)
    for f in violations:
        print(str(f), file=sys.stderr)
    if args.verbose:
        for f in allowed:
            print(f"allowed: {f}")
    n_mod = len({f.path for f in violations})
    if violations:
        print(
            f"\njit-safety lint: {len(violations)} violation(s) in "
            f"{n_mod} module(s). Fix, or — for genuinely host-side code "
            f"— add a `path::qualname` line to "
            f"{os.path.relpath(args.allowlist, REPO)} or a trailing "
            f"`# {WAIVER}` comment.",
            file=sys.stderr,
        )
        return 1
    print(f"jit-safety lint: OK ({len(allowed)} allowlisted hit(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
