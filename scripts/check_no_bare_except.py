#!/usr/bin/env python
"""Lint: no bare ``except:`` clauses in paddle_tpu/, benchmarks/, or
scripts/ — and, in the serving stack, no silent scope cuts.

A bare except swallows KeyboardInterrupt/SystemExit and — worse for a
reliability layer — erases the TYPE of the failure, which is the whole
contract (clients branch on ``ReliabilityError`` subclasses; the chaos
suites assert on them). ``except Exception`` is the floor. Benchmarks
and tooling are covered too: a bench that swallows its own failure
reports numbers for work that never ran.

Scope-cut rule (ISSUE 6, dirs extended to reliability/ + telemetry/ by
ISSUE 7): under the serving/kernel/reliability dirs
(``SCOPE_CUT_DIRS``), every ``raise NotImplementedError("...")`` WITH a
message must point at the ROADMAP item that will lift it (the string
contains "ROADMAP") — that is what kept the paged+mesh and paged+int8
cuts discoverable instead of buried. Deliberate non-cuts (abstract
methods raise bare; API refusals) opt out with a ``# no-roadmap:
<reason>`` comment on the raise line, which is itself grep-able.

That a documented combination still REFUSES is held where people look:
``tests/test_chip_bringup.py::test_documented_refusals`` constructs
each one and reads its message.

Usage: python scripts/check_no_bare_except.py [root ...]
Exit status 1 lists every offending file:line. Wired into the test
suite (tests/test_train_reliability.py) so a regression fails tier-1.
"""
from __future__ import annotations

import ast
import os
import sys

DEFAULT_DIRS = ("paddle_tpu", "benchmarks", "scripts")

# serving/kernel surfaces where a NotImplementedError is (almost
# always) a recorded scope cut — the ROADMAP is its tracking issue.
# reliability/ and telemetry/ joined with the multi-replica router
# (ISSUE 7): scope cuts in the supervisor/failover machinery are
# exactly the kind that silently bite during an incident.
SCOPE_CUT_DIRS = (
    os.path.join("paddle_tpu", "inference"),
    os.path.join("paddle_tpu", "models"),
    os.path.join("paddle_tpu", "ops", "pallas"),
    os.path.join("paddle_tpu", "reliability"),
    os.path.join("paddle_tpu", "telemetry"),
)
OPT_OUT = "no-roadmap:"


def _raise_strings(node):
    """String-literal fragments inside a ``raise NotImplementedError``
    call's arguments (f-strings contribute their constant parts)."""
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            out.append(sub.value)
    return out


def _unpointered_not_implemented(tree, lines, path):
    """[(path, lineno), ...] of messageful NotImplementedError raises
    with no ROADMAP pointer and no ``# no-roadmap:`` opt-out."""
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Raise) or node.exc is None:
            continue
        exc = node.exc
        if not (isinstance(exc, ast.Call)
                and isinstance(exc.func, ast.Name)
                and exc.func.id == "NotImplementedError"):
            continue
        strings = _raise_strings(exc)
        if not strings:
            continue                      # bare/dynamic message: skip
        if any("ROADMAP" in s for s in strings):
            continue
        start = node.lineno - 1
        end = getattr(node, "end_lineno", node.lineno)
        if any(OPT_OUT in lines[i] for i in
               range(max(0, start - 1), min(end, len(lines)))):
            continue
        hits.append((path, node.lineno))
    return hits


def scan(root, repo):
    """(bare_excepts, unpointered_cuts) under ``root``."""
    bare, cuts = [], []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, "rb") as f:
                src = f.read()
            try:
                tree = ast.parse(src, filename=path)
            except SyntaxError as e:
                bare.append((path, e.lineno or 0))
                continue
            for node in ast.walk(tree):
                if isinstance(node, ast.ExceptHandler) \
                        and node.type is None:
                    bare.append((path, node.lineno))
            rel = os.path.relpath(path, repo)
            if any(rel.startswith(d + os.sep) or rel == d
                   for d in SCOPE_CUT_DIRS):
                lines = src.decode("utf-8",
                                   errors="replace").splitlines()
                cuts += _unpointered_not_implemented(tree, lines, path)
    return bare, cuts


def bare_excepts(root):
    """[(path, lineno), ...] of bare ``except:`` handlers under root
    (kept for existing callers)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return scan(root, repo)[0]


def main(argv):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    roots = argv[1:] or [os.path.join(repo, d) for d in DEFAULT_DIRS]
    bare, cuts = [], []
    for root in roots:
        b, c = scan(root, repo)
        bare += b
        cuts += c
    for path, line in bare:
        print(f"{path}:{line}: bare 'except:' — name the exception type "
              "(at least 'except Exception')")
    for path, line in cuts:
        print(f"{path}:{line}: NotImplementedError without a ROADMAP "
              "pointer — name the ROADMAP item that lifts this scope "
              f"cut, or opt out with '# {OPT_OUT} <reason>'")
    if bare or cuts:
        return 1
    print(f"OK: no bare excepts / unpointered scope cuts under "
          f"{', '.join(roots)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
