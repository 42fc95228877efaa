"""AST trace-safety linter for the apex_tpu package.

JAX correctness hazards are invisible to generic linters because they
depend on *where* code runs: ``float(x)`` is fine on the host and a
silent device sync (or a hard ``TracerConversionError``) inside a
``jax.jit``.  This linter reconstructs the traced regions statically —
functions reaching ``jax.jit`` / ``pl.pallas_call`` / ``shard_map`` /
``lax.scan`` bodies, by decorator, call-site reference, or lexical
nesting — and applies trace-discipline rules inside them, plus
package-wide hygiene rules everywhere.

Rules (docs/api/analysis.md for the long-form table):

==========  ================================================================
APX101      host-sync call on a traced value inside a traced region
            (``float()``/``int()``/``bool()``/``.item()``/``.tolist()``/
            ``np.asarray``/``np.array``/``jax.device_get``)
APX102      Python truthiness on a traced value in a boolean
            statement context (``if``/``while``/``assert`` tests,
            including ``not``/``and``/``or`` within them)
APX103      environment read inside a traced region (recompile bomb:
            the flag is baked into the trace, not re-read)
APX201      bare ``except:``
APX202      broad ``except Exception/BaseException`` that neither
            re-raises nor logs through a logger
APX301      ``os.environ``/``os.getenv`` read outside the flag registry
            (route ``APEX_TPU_*`` flags through
            :mod:`apex_tpu.analysis.flags`)
APX501      direct ``jax.shard_map`` / ``jax.experimental.shard_map``
            use (route through :mod:`apex_tpu._compat` — rule exists
            because old jax spells it differently)
APX900      malformed suppression comment (missing ``-- reason``)
==========  ================================================================

Suppression: append ``# apex-lint: disable=APX202 -- <reason>`` to the
offending line (the reason is mandatory), or record the finding's
stable key in the committed baseline file
(``tools/analysis_baseline.txt``) with a trailing ``# reason``.  CI
runs ``python -m apex_tpu.analysis --check`` self-hosted: zero
unsuppressed findings or the build is red.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "lint_paths", "lint_source", "load_baseline",
           "run_check", "DEFAULT_BASELINE"]

DEFAULT_BASELINE = "tools/analysis_baseline.txt"

# Names that put a callee's body inside a trace when a local function is
# passed to them (first positional argument or ``body_fun``-style).
_TRACE_ENTRY_CALLS = {
    "jit", "pjit", "pallas_call", "shard_map", "scan", "while_loop",
    "fori_loop", "cond", "switch", "checkpoint", "remat", "vmap",
    "pmap", "grad", "value_and_grad", "custom_vjp", "custom_jvp",
    "named_call", "eval_shape", "make_jaxpr",
}
# Decorators that make the decorated function body traced.
_TRACE_DECORATORS = {
    "jit", "pjit", "checkpoint", "remat", "vmap", "pmap",
    "custom_vjp", "custom_jvp",
}
# Attribute reads that yield static (host) values even on tracers.
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize", "aval",
                 "sharding", "at"}
# Callables through which taint propagates (module aliases).
_ARRAY_MODULES = {"jnp", "lax", "np"}  # np only via asarray-class sinks
_HOST_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_HOST_SYNC_METHODS = {"item", "tolist", "__float__", "__int__"}
_NP_SYNC_FUNCS = {"asarray", "array", "float32", "float64", "int32",
                  "int64", "asanyarray"}
_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical", "log"}

_SUPPRESS_RE = re.compile(
    r"#\s*apex-lint:\s*disable=([A-Z0-9, ]+?)(?:\s*--\s*(.*?))?\s*$")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One structured lint finding."""

    path: str          # repo-relative, forward slashes
    line: int
    col: int
    rule: str          # e.g. 'APX101'
    severity: str      # 'error' | 'warning'
    message: str
    symbol: str        # stable anchor (function / env var / snippet)

    @property
    def key(self) -> str:
        """Line-number-free identity used by the baseline file."""
        return f"{self.path}:{self.rule}:{self.symbol}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.severity}] {self.message}")


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------

def _suppressions(source: str, path: str) -> Tuple[Dict[int, Set[str]],
                                                   List[Finding]]:
    """Map line -> suppressed rule ids; malformed suppressions become
    APX900 findings so a reason can never be silently omitted."""
    by_line: Dict[int, Set[str]] = {}
    bad: List[Finding] = []
    for i, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        reason = (m.group(2) or "").strip()
        if not reason:
            bad.append(Finding(
                path=path, line=i, col=text.index("#"), rule="APX900",
                severity="error",
                message="suppression without a reason (write "
                        "'# apex-lint: disable=<RULE> -- why')",
                symbol=f"L{i}"))
            continue
        by_line[i] = rules
    return by_line, bad


# ---------------------------------------------------------------------------
# traced-region discovery
# ---------------------------------------------------------------------------

def _tail_name(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' -> 'scan'; 'jit' -> 'jit'."""
    while isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _decorator_traced(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        if _tail_name(target) in _TRACE_DECORATORS:
            return True
        # functools.partial(jax.jit, ...) as decorator
        if isinstance(dec, ast.Call) and _tail_name(dec.func) == "partial":
            for a in dec.args:
                if _tail_name(a) in _TRACE_DECORATORS:
                    return True
    return False


class _TraceRegions(ast.NodeVisitor):
    """Collect function defs plus the set traced by decorator or by
    being passed (as a ``Name``) into a trace-entry call anywhere in
    the module."""

    def __init__(self) -> None:
        self.functions: List[ast.AST] = []
        # name -> (static positional prefix, static kwarg names): args
        # bound by functools.partial are PYTHON values at trace time,
        # not tracers (the pallas-kernel config-prefix idiom)
        self.traced_names: Dict[str, Tuple[int, Set[str]]] = {}
        self.decorated: List[ast.AST] = []

    def _record(self, name: str, prefix: int, kwargs: Set[str]) -> None:
        old = self.traced_names.get(name)
        if old is not None:
            # multiple references: a positional is static only if bound
            # at EVERY site (min); a keyword bound by partial anywhere
            # is config — sites that omit it use the static default
            prefix = min(prefix, old[0])
            kwargs = kwargs | old[1]
        self.traced_names[name] = (prefix, kwargs)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.functions.append(node)
        if _decorator_traced(node):
            self.decorated.append(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        callee = _tail_name(node.func)
        if callee in _TRACE_ENTRY_CALLS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    self._record(arg.id, 0, set())
                if (isinstance(arg, ast.Call)
                        and _tail_name(arg.func) == "partial"):
                    fn_args = arg.args
                    if fn_args and isinstance(fn_args[0], ast.Name):
                        self._record(
                            fn_args[0].id, len(fn_args) - 1,
                            {kw.arg for kw in arg.keywords if kw.arg})
        self.generic_visit(node)


def _traced_functions(
        tree: ast.AST) -> List[Tuple[ast.AST, int, Set[str]]]:
    """(function, static positional prefix, static kwarg names) for
    every function def whose body is traced, including functions
    lexically nested inside traced ones."""
    finder = _TraceRegions()
    finder.visit(tree)
    traced: List[Tuple[ast.AST, int, Set[str]]] = [
        (f, 0, set()) for f in finder.decorated]
    traced += [(f, *finder.traced_names[f.name])
               for f in finder.functions
               if getattr(f, "name", None) in finder.traced_names
               and f not in finder.decorated]
    # lexical nesting: children of traced functions are traced
    seen = {id(f) for f, _, _ in traced}
    frontier = [f for f, _, _ in traced]
    while frontier:
        fn = frontier.pop()
        for node in ast.walk(fn):
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda))
                    and node is not fn and id(node) not in seen):
                seen.add(id(node))
                traced.append((node, 0, set()))
                frontier.append(node)
    return traced


# ---------------------------------------------------------------------------
# taint walk inside one traced function
# ---------------------------------------------------------------------------

class _Taint:
    """Conservative value-taint: parameters of a traced function are
    traced values; taint flows through arithmetic, subscripts, jnp/lax
    calls and plain assignments.  ``.shape``-class attributes and
    non-array calls launder it (static at trace time)."""

    def __init__(self, fn: ast.AST, static_prefix: int = 0,
                 static_kwargs: Optional[Set[str]] = None) -> None:
        self.tainted: Set[str] = set()
        static_kwargs = static_kwargs or set()
        args = getattr(fn, "args", None)
        if args is not None:
            positional = list(args.posonlyargs) + list(args.args)
            for i, a in enumerate(positional):
                if i < static_prefix or a.arg in static_kwargs:
                    continue  # functools.partial-bound: static config
                if a.arg not in ("self", "cls"):
                    self.tainted.add(a.arg)
            for a in args.kwonlyargs:
                if a.arg not in static_kwargs:
                    self.tainted.add(a.arg)
            if args.vararg:
                self.tainted.add(args.vararg.arg)
            if args.kwarg:
                self.tainted.add(args.kwarg.arg)

    def expr_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.expr_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr_tainted(node.value)
        if isinstance(node, (ast.BinOp,)):
            return (self.expr_tainted(node.left)
                    or self.expr_tainted(node.right))
        if isinstance(node, ast.UnaryOp):
            return self.expr_tainted(node.operand)
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False          # identity tests are static
            return (self.expr_tainted(node.left)
                    or any(self.expr_tainted(c) for c in node.comparators))
        if isinstance(node, ast.BoolOp):
            return any(self.expr_tainted(v) for v in node.values)
        if isinstance(node, ast.IfExp):
            return (self.expr_tainted(node.body)
                    or self.expr_tainted(node.orelse))
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr_tainted(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.expr_tainted(node.value)
        if isinstance(node, ast.Call):
            # jnp./lax. results stay traced; anything else launders
            # (len(), isinstance(), int-shape helpers, user calls we
            # cannot see into — conservative against false positives).
            head = node.func
            root = head
            while isinstance(root, ast.Attribute):
                root = root.value
            if (isinstance(root, ast.Name)
                    and root.id in ("jnp", "lax")):
                return True
            return False
        return False

    def assign(self, node: ast.AST) -> None:
        targets: List[ast.AST] = []
        value = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AugAssign):
            targets, value = [node.target], node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        if value is None:
            return
        is_tainted = self.expr_tainted(value)
        for t in targets:
            # a store into ``ref[i, lo:hi]`` assigns ``ref``: the index
            # names are read there, not written
            while isinstance(t, (ast.Subscript, ast.Attribute)):
                t = t.value
            for name in ast.walk(t):
                if isinstance(name, ast.Name):
                    if is_tainted:
                        self.tainted.add(name.id)
                    else:
                        self.tainted.discard(name.id)


def _is_env_read(node: ast.Call | ast.Attribute | ast.Subscript) -> bool:
    """os.environ[...] / os.environ.get(...) / os.getenv(...) /
    environ.get(...)."""
    def names(n: ast.AST) -> str:
        if isinstance(n, ast.Attribute):
            return names(n.value) + "." + n.attr
        if isinstance(n, ast.Name):
            return n.id
        return "?"

    if isinstance(node, ast.Call):
        dotted = names(node.func)
        return dotted.endswith("getenv") or ".environ.get" in dotted \
            or dotted == "environ.get"
    if isinstance(node, ast.Subscript):
        node = node.value
    dotted = names(node)
    return dotted.endswith(".environ") or dotted == "environ"


def _env_symbol(node: ast.AST) -> str:
    """Best-effort env var name for the finding key."""
    target = None
    if isinstance(node, ast.Call) and node.args:
        target = node.args[0]
    elif isinstance(node, ast.Subscript):
        target = node.slice
    if isinstance(target, ast.Constant) and isinstance(target.value, str):
        return target.value
    return "dynamic"


# ---------------------------------------------------------------------------
# the lint pass
# ---------------------------------------------------------------------------

def lint_source(source: str, path: str, *,
                flags_module: bool = False) -> List[Finding]:
    """Lint one file's source.  ``flags_module`` marks the registry
    itself (its env read is the one legal one)."""
    findings: List[Finding] = []
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(path=path, line=e.lineno or 0, col=e.offset or 0,
                        rule="APX000", severity="error",
                        message=f"syntax error: {e.msg}", symbol="syntax")]
    suppressed, bad_suppressions = _suppressions(source, path)
    findings.extend(bad_suppressions)

    def emit(node: ast.AST, rule: str, message: str, symbol: str,
             severity: str = "error") -> None:
        line = getattr(node, "lineno", 0)
        for probe in (line, getattr(node, "end_lineno", line)):
            if rule in suppressed.get(probe, ()):  # inline suppression
                return
        findings.append(Finding(path=path, line=line,
                                col=getattr(node, "col_offset", 0),
                                rule=rule, severity=severity,
                                message=message, symbol=symbol))

    # --- traced-region rules ---------------------------------------------
    traced_env_nodes: Set[int] = set()  # APX103 sites: skip dup APX301

    def fname(fn: ast.AST) -> str:
        return getattr(fn, "name", "<lambda>")

    for fn, static_prefix, static_kwargs in _traced_functions(tree):
        taint = _Taint(fn, static_prefix, static_kwargs)
        # two passes: assignments first (simple flow), then checks —
        # good enough for the straight-line bodies kernels actually have
        for node in ast.walk(fn):
            taint.assign(node)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                callee = node.func
                if (isinstance(callee, ast.Name)
                        and callee.id in _HOST_SYNC_BUILTINS
                        and node.args
                        and taint.expr_tainted(node.args[0])):
                    emit(node, "APX101",
                         f"{callee.id}() on a traced value inside "
                         f"traced function '{fname(fn)}' forces a host "
                         f"sync / TracerConversionError",
                         f"{fname(fn)}.{callee.id}")
                if (isinstance(callee, ast.Attribute)
                        and callee.attr in _HOST_SYNC_METHODS
                        and taint.expr_tainted(callee.value)):
                    emit(node, "APX101",
                         f".{callee.attr}() on a traced value inside "
                         f"traced function '{fname(fn)}'",
                         f"{fname(fn)}.{callee.attr}")
                if (isinstance(callee, ast.Attribute)
                        and callee.attr in _NP_SYNC_FUNCS
                        and isinstance(callee.value, ast.Name)
                        and callee.value.id in ("np", "numpy")
                        and node.args
                        and taint.expr_tainted(node.args[0])):
                    emit(node, "APX101",
                         f"np.{callee.attr}() on a traced value inside "
                         f"traced function '{fname(fn)}' materializes "
                         f"on host",
                         f"{fname(fn)}.np.{callee.attr}")
                if (isinstance(callee, ast.Attribute)
                        and callee.attr == "device_get"):
                    emit(node, "APX101",
                         f"jax.device_get inside traced function "
                         f"'{fname(fn)}'", f"{fname(fn)}.device_get")
                if _is_env_read(node):
                    traced_env_nodes.add(id(node))
                    emit(node, "APX103",
                         f"environment read inside traced function "
                         f"'{fname(fn)}' is baked into the trace "
                         f"(recompile bomb / stale flag)",
                         f"{fname(fn)}.{_env_symbol(node)}")
            if isinstance(node, ast.Subscript) and _is_env_read(node):
                # environ.get(...) is handled above as a Call
                traced_env_nodes.add(id(node))
                emit(node, "APX103",
                     f"os.environ[...] inside traced function "
                     f"'{fname(fn)}'",
                     f"{fname(fn)}.{_env_symbol(node)}")
            if isinstance(node, (ast.If, ast.While)):
                if taint.expr_tainted(node.test):
                    emit(node, "APX102",
                         f"Python branch on a traced value in "
                         f"'{fname(fn)}' — use jnp.where/lax.cond",
                         f"{fname(fn)}.branch")
            if isinstance(node, ast.Assert):
                if taint.expr_tainted(node.test):
                    emit(node, "APX102",
                         f"assert on a traced value in '{fname(fn)}' "
                         f"— tracers have no truth value",
                         f"{fname(fn)}.assert")

    # --- whole-file rules --------------------------------------------------
    def _catches_broad(handler_type: ast.AST) -> bool:
        if isinstance(handler_type, ast.Tuple):
            return any(_catches_broad(e) for e in handler_type.elts)
        return _tail_name(handler_type) in ("Exception", "BaseException")

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler):
            if node.type is None:
                emit(node, "APX201",
                     "bare 'except:' swallows KeyboardInterrupt and "
                     "SystemExit — name the exception types",
                     f"bare_except.L{node.lineno}")
            elif _catches_broad(node.type):
                body_reraises = any(
                    isinstance(s, ast.Raise) for s in node.body)
                body_logs = any(
                    isinstance(c, ast.Call)
                    and isinstance(c.func, ast.Attribute)
                    and c.func.attr in _LOG_METHODS
                    for s in node.body for c in ast.walk(s))
                if not body_reraises and not body_logs:
                    emit(node, "APX202",
                         f"broad 'except "
                         f"{_tail_name(node.type) or 'Exception (in tuple)'}"
                         f"' that "
                         f"neither re-raises nor logs — narrow it, log "
                         f"via utils.log_util, or suppress with a "
                         f"reason",
                         f"broad_except.L{node.lineno}", severity="error")
        if isinstance(node, ast.Call) and _is_env_read(node) \
                and not flags_module \
                and id(node) not in traced_env_nodes:
            emit(node, "APX301",
                 "environment read outside the flag registry — declare "
                 "the flag in apex_tpu/analysis/flags.py and use the "
                 "typed accessors",
                 _env_symbol(node))
        if isinstance(node, ast.Subscript) and _is_env_read(node) \
                and not flags_module \
                and id(node) not in traced_env_nodes:
            emit(node, "APX301",
                 "os.environ[...] outside the flag registry",
                 _env_symbol(node))
        if path.endswith("_compat.py"):
            continue  # the shim is the one legal shard_map resolver
        if isinstance(node, ast.Attribute) and node.attr == "shard_map":
            root = node.value
            dotted = []
            cur: ast.AST = node
            while isinstance(cur, ast.Attribute):
                dotted.append(cur.attr)
                cur = cur.value
            if isinstance(cur, ast.Name) and cur.id == "jax":
                emit(node, "APX501",
                     "direct jax.shard_map use — import it from "
                     "apex_tpu._compat (old jax spells it "
                     "jax.experimental.shard_map with check_rep)",
                     "jax.shard_map")
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in ("jax", "jax.experimental",
                       "jax.experimental.shard_map") and any(
                    a.name == "shard_map" for a in node.names):
                emit(node, "APX501",
                     f"import shard_map from {mod} — use "
                     f"apex_tpu._compat.shard_map",
                     f"import.{mod}")
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "jax.experimental.shard_map":
                    emit(node, "APX501",
                         "import jax.experimental.shard_map — use "
                         "apex_tpu._compat.shard_map",
                         "import.jax.experimental.shard_map")
    return findings


# ---------------------------------------------------------------------------
# repo walk + baseline
# ---------------------------------------------------------------------------

def _iter_py(root: Path) -> Iterable[Path]:
    for p in sorted(root.rglob("*.py")):
        if "__pycache__" in p.parts:
            continue
        yield p


# Trees outside the package that must stay routed through _compat
# (APX501 only — tests/benches legitimately read env vars and catch
# broadly): the old-jax tier-1 failures this repo cleared come back
# the moment a test reintroduces a bare jax.shard_map.
COMPAT_SCAN_PATHS = ("tests", "examples", "__graft_entry__.py")


def lint_paths(package_root: str = "apex_tpu", *,
               repo_root: str = ".",
               paths: Optional[Sequence[str]] = None) -> List[Finding]:
    """Lint every .py under ``package_root`` (repo-relative), plus the
    compat-routing rule (APX501) over :data:`COMPAT_SCAN_PATHS`.

    ``paths`` restricts the walk to the named repo-relative files —
    the changed-file pre-commit fast path (``--check --paths ...``,
    sub-second where the full walk costs seconds).  Each named file
    gets exactly the rule set the full walk would give it: full lint
    under ``package_root``, APX501-only under
    :data:`COMPAT_SCAN_PATHS`, nothing elsewhere (a data file or doc
    is not lint surface, not an error).  Missing files are skipped —
    a deleted file carries no findings, and pre-commit hands deletions
    over too."""
    repo = Path(repo_root).resolve()
    findings: List[Finding] = []

    def _in_package(rel: str) -> bool:
        return rel == package_root or rel.startswith(package_root + "/")

    def _compat_scope(rel: str) -> bool:
        return any(rel == entry or rel.startswith(entry + "/")
                   for entry in COMPAT_SCAN_PATHS)

    if paths is not None:
        for name in paths:
            p = repo / name
            if not p.exists() or p.suffix != ".py":
                continue
            try:
                rel = p.resolve().relative_to(repo).as_posix()
            except ValueError:
                continue  # outside the repo: not lint surface
            if _in_package(rel):
                findings.extend(lint_source(
                    p.read_text(), rel,
                    flags_module=rel.endswith("analysis/flags.py")))
            elif _compat_scope(rel):
                findings.extend(
                    f for f in lint_source(p.read_text(), rel)
                    if f.rule == "APX501")
        return findings

    for p in _iter_py(repo / package_root):
        rel = p.relative_to(repo).as_posix()
        is_flags = rel.endswith("analysis/flags.py")
        findings.extend(lint_source(p.read_text(), rel,
                                    flags_module=is_flags))
    for entry in COMPAT_SCAN_PATHS:
        target = repo / entry
        files = [target] if target.suffix == ".py" else             list(_iter_py(target)) if target.exists() else []
        for p in files:
            if not p.exists():
                continue
            rel = p.relative_to(repo).as_posix()
            findings.extend(
                f for f in lint_source(p.read_text(), rel)
                if f.rule == "APX501")
    return findings


def load_baseline(path: str = DEFAULT_BASELINE, *,
                  repo_root: str = ".") -> Dict[str, str]:
    """Baseline file -> {finding.key: reason}.  Lines:
    ``path:RULE:symbol  # reason``; '#'-prefixed lines are comments."""
    p = Path(repo_root) / path
    if not p.exists():
        return {}
    out: Dict[str, str] = {}
    for raw in p.read_text().splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        key, _, reason = line.partition("#")
        out[key.strip()] = reason.strip()
    return out


_BASELINE_HEADER = (
    "# apex_tpu.analysis baseline — pre-existing findings accepted",
    "# with a reason.  New findings do NOT belong here by default:",
    "# fix them or suppress inline with '# apex-lint: disable=...'.",
    "# Format: <path>:<rule>:<symbol>  # <reason>",
)


def write_baseline(findings: Sequence[Finding],
                   path: str = DEFAULT_BASELINE, *,
                   repo_root: str = ".",
                   header: Sequence[str] = _BASELINE_HEADER) -> None:
    """Serialize a baseline file (one implementation — the
    concurrency auditor delegates here with its own header/path), with
    curated reasons for already-listed keys preserved."""
    p = Path(repo_root) / path
    existing = load_baseline(path, repo_root=repo_root)
    lines = list(header)
    for key in sorted(set(fi.key for fi in findings)):
        reason = existing.get(key) or "accepted pre-existing finding"
        lines.append(f"{key}  # {reason}")
    p.write_text("\n".join(lines) + "\n")


def run_check(package_root: str = "apex_tpu", *,
              baseline: str = DEFAULT_BASELINE,
              repo_root: str = ".",
              paths: Optional[Sequence[str]] = None
              ) -> Tuple[List[Finding], List[str]]:
    """(unsuppressed findings, stale baseline keys).

    With ``paths`` (the pre-commit fast path) only those files are
    linted; the kernel-parity audit (whole-repo by construction) and
    baseline-staleness judgment (only a full walk can prove a
    suppression dead) are skipped — full CI keeps both.
    """
    findings = lint_paths(package_root, repo_root=repo_root,
                          paths=paths)
    if paths is None:
        from .parity import audit_kernel_parity

        findings.extend(audit_kernel_parity(repo_root=repo_root))
    base = load_baseline(baseline, repo_root=repo_root)
    live_keys = {f.key for f in findings}
    unsuppressed = [f for f in findings if f.key not in base]
    stale = ([] if paths is not None
             else [k for k in base if k not in live_keys])
    return unsuppressed, stale
