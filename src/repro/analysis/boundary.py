"""Layer 1: repo-specific AST lint over the whole tree.

Four rules, each enforcing an invariant the ROADMAP used to state only
in prose:

* **BND001** — ``jax.experimental.*`` (Pallas, shard_map's old home,
  anything unstable) may be imported or referenced only from the two
  version-drift shims, ``repro/kernels/pallas_compat.py`` and
  ``repro/compat.py``.  Everything else rides the shims, so a jax bump
  is a two-file change.
* **BND002** — ``jax.shard_map`` likewise: only ``repro/compat.py``
  may touch it, so one place sets its varying-axes check.
* **PUR001** — modules under ``repro/kernels/`` and ``repro/core/``
  hold eval bodies and counter plumbing whose outputs must be a pure
  function of (key, counters, params): no wall-clock (``time``),
  stateful RNG (``random``, ``np.random``), ``datetime``, or host I/O
  (``open``/``input``).  Host-side drivers (``launch/``, ``service/``,
  benchmarks) are out of scope.
* **F64001** — no ``jnp.float64`` (or ``astype``/``dtype='float64'``)
  in ``repro/kernels/`` / ``repro/core/``: accumulators are f32 by
  contract (TPU has no fast f64, and the WAL journals exact f32 bits).
  Host-side ``np.float64`` (analytic references, static metadata) is
  fine and not flagged.
* **OBS001** — modules under ``repro/service/`` and ``repro/obs/``
  read the wall clock only through the ``repro/obs/clock.py`` shim (no
  direct ``time`` import or ``time.*`` call): trace timestamps, metric
  latencies and fake-clock tests must all observe the same clock.
  Kernels/core stay wholly clock-free under the stricter PUR001;
  standalone launchers and ``distributed/`` are out of scope.
* **RES001** — modules under ``repro/service/`` retry, back off and
  sleep only through ``repro/service/resilience.py``: importing the
  ad-hoc ``run_with_restarts`` loop or calling any ``.sleep(...)``
  elsewhere in the service is flagged.  One policy object owns attempt
  budgets, deterministic jitter and deadline clamping — scattered retry
  loops are exactly how tickets end up hanging past their deadline.

Escape hatch: append ``# analysis: ignore[RULE]`` (comma-separate for
several rules) to the offending line.  Use it to *document* a deliberate
exception, never to silence a rule you don't understand — the rule ID
makes every exemption greppable.

The linter is pure ``ast`` + stdlib: it never imports the files it
checks, so fixture files seeded with violations are safe to scan.
"""

from __future__ import annotations

import ast
import os
import re
from pathlib import Path

from repro.analysis.violations import Violation

# Files allowed to touch jax.experimental.* / jax.shard_map (BND001/2).
BOUNDARY_ALLOWED = (
    "repro/kernels/pallas_compat.py",
    "repro/compat.py",
)

# Path fragments marking purity-scoped modules (PUR001/F64001).  A
# segment match (not a suffix match) so test fixtures laid out under
# ``fixtures/kernels/`` / ``fixtures/core/`` are scoped identically.
PURE_SCOPE_SEGMENTS = ("kernels", "core")

# Modules whose import into a pure scope is a PUR001 violation.
_IMPURE_MODULES = ("time", "random", "datetime")

# Path fragments marking clock-shim-scoped modules (OBS001), and the
# one file allowed to touch ``time`` inside them.  Segment match, like
# PURE_SCOPE_SEGMENTS, so ``fixtures/service/`` fixtures scope too.
OBS_SCOPE_SEGMENTS = ("service", "obs")
CLOCK_SHIM_SUFFIX = "obs/clock.py"

# Path fragments marking retry-policy-scoped modules (RES001), and the
# one file allowed to run retry loops and sleep inside them.
RES_SCOPE_SEGMENTS = ("service",)
RESILIENCE_SUFFIX = "service/resilience.py"

# The ad-hoc retry entry point RES001 bans outside the policy module.
_ADHOC_RETRY = "run_with_restarts"

# Builtin calls that do host I/O.
_IO_CALLS = ("open", "input")

# Seed model-config data modules (chatglm/deepseek/...) kept only for
# the model-stack smoke tests; lint-exempt so the clean-tree gate
# reflects the integration service we actually ship.  Mirrored by the
# ruff exclude in pyproject.toml.
DEFAULT_EXCLUDES = ("repro/configs/",)

_IGNORE_RE = re.compile(r"#\s*analysis:\s*ignore\[([A-Za-z0-9_,\s]+)\]")


def _posix(path) -> str:
    return str(path).replace(os.sep, "/")


def _is_boundary_shim(path: str) -> bool:
    return any(path.endswith(suffix) for suffix in BOUNDARY_ALLOWED)


def _in_pure_scope(path: str) -> bool:
    parts = path.split("/")
    return any(seg in parts[:-1] for seg in PURE_SCOPE_SEGMENTS)


def _in_obs_scope(path: str) -> bool:
    if path.endswith(CLOCK_SHIM_SUFFIX):
        return False     # the shim itself wraps ``time``
    parts = path.split("/")
    return any(seg in parts[:-1] for seg in OBS_SCOPE_SEGMENTS)


def _in_res_scope(path: str) -> bool:
    if path.endswith(RESILIENCE_SUFFIX):
        return False     # the policy module itself retries and sleeps
    parts = path.split("/")
    return any(seg in parts[:-1] for seg in RES_SCOPE_SEGMENTS)


def _dotted(node: ast.AST) -> str | None:
    """'a.b.c' for an Attribute/Name chain, None for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _ignored_rules(lines: list[str], lineno: int) -> set[str]:
    if not 1 <= lineno <= len(lines):
        return set()
    m = _IGNORE_RE.search(lines[lineno - 1])
    if not m:
        return set()
    return {r.strip() for r in m.group(1).split(",") if r.strip()}


class _Checker(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.shim = _is_boundary_shim(path)
        self.pure = _in_pure_scope(path)
        self.obs_scope = _in_obs_scope(path)
        self.res_scope = _in_res_scope(path)
        self.found: list[Violation] = []

    def _flag(self, rule: str, node: ast.AST, message: str) -> None:
        self.found.append(Violation(rule=rule, path=self.path,
                                    line=node.lineno, message=message))

    # -- imports --------------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            self._check_module(node, alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        self._check_module(node, mod)
        if mod == "jax" and not self.shim:
            for alias in node.names:
                if alias.name == "shard_map":
                    self._flag("BND002", node,
                               "import jax.shard_map via repro.compat, "
                               "not directly")
        if self.res_scope:
            for alias in node.names:
                if alias.name == _ADHOC_RETRY:
                    self._flag("RES001", node,
                               f"import of {_ADHOC_RETRY!r} in a service "
                               "module: retries go through "
                               "repro.service.resilience.run_with_policy "
                               "(one policy, deterministic jitter, "
                               "deadline-aware)")
        self.generic_visit(node)

    def _check_module(self, node: ast.AST, mod: str) -> None:
        if (mod == "jax.experimental"
                or mod.startswith("jax.experimental.")) and not self.shim:
            self._flag("BND001", node,
                       f"import of {mod!r} outside the compat shims "
                       "(use repro.kernels.pallas_compat / repro.compat)")
        if self.pure and (mod in _IMPURE_MODULES
                          or any(mod.startswith(m + ".")
                                 for m in _IMPURE_MODULES)):
            self._flag("PUR001", node,
                       f"import of {mod!r} in a purity-scoped module "
                       "(eval outputs must be a pure function of "
                       "key/counters/params)")
        if self.obs_scope and (mod == "time" or mod.startswith("time.")):
            self._flag("OBS001", node,
                       f"import of {mod!r} in a service/obs module: go "
                       "through repro.obs.clock (the single wall-clock "
                       "shim) so trace timestamps and fake-clock tests "
                       "stay consistent")

    # -- attribute chains -----------------------------------------------------
    def visit_Attribute(self, node: ast.Attribute) -> None:
        chain = _dotted(node)
        if chain is not None:
            if (chain.startswith("jax.experimental")
                    and not self.shim):
                self._flag("BND001", node,
                           f"reference to {chain!r} outside the compat "
                           "shims")
            elif chain == "jax.shard_map" and not self.shim:
                self._flag("BND002", node,
                           "use repro.compat.shard_map, not "
                           "jax.shard_map")
            if self.pure:
                if chain in ("np.random", "numpy.random") or chain.startswith(
                        ("np.random.", "numpy.random.")):
                    self._flag("PUR001", node,
                               f"stateful host RNG {chain!r} in a "
                               "purity-scoped module (use counter-based "
                               "repro.core.rng)")
                if chain in ("jnp.float64", "jax.numpy.float64"):
                    self._flag("F64001", node,
                               "float64 on an accumulator path "
                               "(deposits are exact f32; TPU has no "
                               "fast f64)")
            if self.obs_scope and chain.startswith("time."):
                self._flag("OBS001", node,
                           f"wall-clock read {chain!r} in a service/obs "
                           "module: use repro.obs.clock")
            if self.res_scope and chain.endswith("." + _ADHOC_RETRY):
                self._flag("RES001", node,
                           f"reference to {chain!r} in a service module: "
                           "retries go through "
                           "repro.service.resilience.run_with_policy")
            # a complete chain is all Names/Attributes: recursing would
            # re-flag its sub-chains (jax.experimental.pallas AND
            # jax.experimental) on the same line
            return
        self.generic_visit(node)

    # -- calls ----------------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        if (self.res_scope and isinstance(node.func, ast.Attribute)
                and node.func.attr == "sleep"):
            self._flag("RES001", node,
                       "ad-hoc sleep in a service module: backoff pauses "
                       "belong to repro.service.resilience (jittered, "
                       "clamped to the request deadline)")
        if self.pure:
            if isinstance(node.func, ast.Name) and node.func.id in _IO_CALLS:
                self._flag("PUR001", node,
                           f"host I/O call {node.func.id}() in a "
                           "purity-scoped module")
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"
                    and any(_is_f64_const(a) for a in node.args)):
                self._flag("F64001", node,
                           "astype('float64') on an accumulator path")
            for kw in node.keywords:
                if kw.arg == "dtype" and _is_f64_const(kw.value):
                    self._flag("F64001", node,
                               "dtype='float64' on an accumulator path")
        self.generic_visit(node)


def _is_f64_const(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) and node.value == "float64"


def check_source(source: str, path: str) -> list[Violation]:
    """Lint one file's source; ``path`` scopes the rules (see module
    docstring) and labels the violations."""
    path = _posix(path)
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [Violation(rule="BND001", path=path, line=exc.lineno or 0,
                          message=f"unparseable file: {exc.msg}")]
    checker = _Checker(path)
    checker.visit(tree)
    lines = source.splitlines()
    return [v for v in checker.found
            if v.rule not in _ignored_rules(lines, v.line)]


def check_file(path) -> list[Violation]:
    with open(path, encoding="utf-8") as f:
        return check_source(f.read(), _posix(path))


def iter_python_files(root):
    root = Path(root)
    if root.is_file():
        yield root
        return
    for path in sorted(root.rglob("*.py")):
        if any(part.startswith(".") for part in path.parts):
            continue
        yield path


def check_paths(paths, *, excludes: tuple[str, ...] = DEFAULT_EXCLUDES
                ) -> list[Violation]:
    """Lint every ``*.py`` under each path (files or directories)."""
    found: list[Violation] = []
    for root in paths:
        for path in iter_python_files(root):
            posix = _posix(path)
            if any(ex in posix for ex in excludes):
                continue
            found.extend(check_file(path))
    return found
