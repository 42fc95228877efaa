"""Compiled-graph auditor: donation, dtype promotion, collective
census, host transfers, and peak-live-memory over lowered jaxprs.

The AST linter (:mod:`.linter`) proves the Python *source* is
trace-safe; this module audits what the tracer and XLA actually
*emitted* for the framework's registered entry points
(:mod:`apex_tpu.testing.entry_points`) — the artifact layer where a
missed ``donate_argnums``, a silent bf16→f32 promotion, or a collective
added by a transposition is invisible to any source-level pass.  It is
the static, CI-time counterpart of the runtime sanitizer: the
transfer-guard can only catch a compiled-in host callback after
deployment; here it fails the build.

Rules (registered in :mod:`.rules`, table in docs/api/analysis.md):

* **APX601 missed donation** — an input buffer the entry registry
  declares dead after the call, with a shape/dtype-matching output,
  but no ``tf.aliasing_output`` attribute in the lowered StableHLO
  module.  The attribute is the ground truth: it is what the runtime
  buffer-donation pass consumes, so auditing it catches a
  ``jax.jit`` that silently dropped (or never had) ``donate_argnums``.
* **APX602 silent dtype promotion** — a ``convert_element_type``
  bf16/f16 → f32 inside an O4/O5-policy entry whose provenance is not
  a sanctioned-fp32 region (layer-norm stats, softmax, loss, amp
  machinery): an upcast the precision policy did not ask for.
* **APX603 collective census** — every psum / all_gather /
  reduce_scatter / all_to_all / ppermute with element counts and bytes
  moved per step (scan bodies multiply by trip count), diffed against
  the committed ``tools/hlo_baseline.json``.  A new collective kind,
  more ops, or >10% byte growth fails CI with the offending op's
  jaxpr provenance; shrinks fail too (refresh the baseline — it only
  stays meaningful if it tracks reality).
* **APX604 host transfer** — callback/infeed/outfeed ops compiled into
  the graph (``pure_callback`` / ``io_callback`` / ``debug_callback``):
  a host round-trip every step.
* **APX606 dequantized weight residency** — the Q8 analogue of
  APX602: a ``convert_element_type`` int8 → f32/bf16 of a
  weight-sized tensor inside a Q8-policy entry whose provenance is
  not the quant kernel family (``ops/quant_matmul.py``, where dequant
  is tile-local in VMEM) or the int8-KV decode kernels.  An
  HLO-visible dense copy of an int8 operand means the graph
  materializes the fp32 weights it was quantized to avoid — the
  bandwidth win is silently forfeited.
* **APX605 peak-live-memory estimate** — buffer liveness over the
  lowered jaxpr (inputs+consts live at entry, equation outputs
  allocated in order, buffers freed after their last use, call-like
  sub-jaxprs contributing their internal excess), gated ±10% against
  the baseline per entry point.

Suppression uses the PR-5 machinery: the committed findings baseline
``tools/hlo_findings.txt`` (same ``path:RULE:symbol  # reason`` format,
empty — every finding at introduction was fixed), stale entries fail.
CLI: ``python -m apex_tpu.analysis --check-hlo`` /
``--update-hlo-baseline`` (tools/ci.sh step 7, on CPU lowerings with
an 8-device host-platform mesh for the multichip entries).
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from .linter import Finding, load_baseline

__all__ = ["CollectiveOp", "EntryAudit", "audit_entry_points",
           "run_hlo_check", "write_hlo_baseline", "peak_live_bytes",
           "DEFAULT_HLO_BASELINE", "DEFAULT_HLO_FINDINGS"]

DEFAULT_HLO_BASELINE = "tools/hlo_baseline.json"
DEFAULT_HLO_FINDINGS = "tools/hlo_findings.txt"

# jaxpr primitives that move data across devices (census classes).
COLLECTIVE_PRIMS = {"psum", "pmax", "pmin", "all_gather",
                    "reduce_scatter", "all_to_all", "ppermute",
                    "pgather"}
# jaxpr primitives XLA services from the host every execution.
HOST_TRANSFER_PRIMS = {"pure_callback", "io_callback",
                       "debug_callback", "debug_print", "infeed",
                       "outfeed"}
# Low-precision source dtypes for the promotion rule.
_LOWP = ("bfloat16", "float16")

# APX606: modules whose int8 -> float converts are the POINT — the
# quant matmul family dequantizes tile-locally (its registered twin is
# the sanctioned XLA fallback on CPU lowerings), and the paged decode
# kernels dequantize int8 KV rows the same way.  Everywhere else a
# weight-sized int8 -> f32/bf16 convert is a materialized dequant.
Q8_DEQUANT_REGIONS = ("apex_tpu/ops/quant_matmul.py",
                      "apex_tpu/ops/flash_decode.py")
# ...and converts below this are scale vectors / scalars, not weights.
_DEQUANT_MIN_BYTES = 1024

# APX601 ignores buffers below this: donating a scalar loss-scale
# saves nothing, and matching tiny scalars by (shape, dtype) is pure
# coincidence.  Donation economics start at real parameter buffers.
_DONATION_MIN_BYTES = 1024

_GROWTH_TOL = 0.10  # APX603/605 byte tolerance, both directions


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------

def _core():
    import jax.extend.core

    return jax.extend.core


def _sub_jaxprs(eqn) -> Iterator[Any]:
    """Inner jaxprs of a call-like equation (pjit/scan/cond/shard_map/
    custom_vjp/pallas_call/... — anything carrying a jaxpr param)."""
    core = _core()
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for s in vals:
            if isinstance(s, core.ClosedJaxpr):
                yield s.jaxpr
            elif isinstance(s, core.Jaxpr):
                yield s


def _iter_eqns(jaxpr, mult: int = 1) -> Iterator[Tuple[Any, int]]:
    """Yield ``(eqn, trip_multiplier)`` over a jaxpr and every nested
    jaxpr.  A ``scan`` body's equations run ``length`` times per
    execution of the outer program — the census must price them per
    *step*, not per trace occurrence."""
    for eqn in jaxpr.eqns:
        yield eqn, mult
        inner_mult = mult
        if eqn.primitive.name == "scan":
            inner_mult = mult * int(eqn.params.get("length", 1) or 1)
        elif eqn.primitive.name == "while":
            # trip count is dynamic; price one iteration (documented
            # under-estimate, flagged in the op record)
            inner_mult = mult
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub, inner_mult)


def _aval_bytes(aval) -> int:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * int(dtype.itemsize)


def _provenance(eqn, repo_root: Path) -> Tuple[str, int, str]:
    """(repo-relative file, line, function) of the innermost frame
    under the repo for this equation; the innermost user frame
    otherwise; ``("<unknown>", 0, "?")`` when the trace kept nothing."""
    try:
        from jax._src import source_info_util

        frames = list(source_info_util.user_frames(
            eqn.source_info.traceback))
    except Exception:  # apex-lint: disable=APX202 -- provenance is best-effort: a moved jax internal must degrade to "<unknown>", not kill the audit
        frames = []
    pick = None
    root = str(repo_root)
    for fr in frames:  # innermost-first
        if fr.file_name.startswith(root):
            pick = fr
            break
    if pick is None and frames:
        pick = frames[0]
    if pick is None:
        return "<unknown>", 0, "?"
    fname = pick.file_name
    if fname.startswith(root):
        fname = str(Path(fname).relative_to(repo_root).as_posix())
    # jax reports the qualified name; findings and baselines key on
    # the bare function name
    return (fname, int(pick.start_line),
            pick.function_name.rsplit(".", 1)[-1])


# ---------------------------------------------------------------------------
# APX605: peak-live-memory estimate from buffer liveness
# ---------------------------------------------------------------------------

def peak_live_bytes(jaxpr) -> int:
    """Estimate the peak of live buffer bytes over one execution of
    ``jaxpr`` (a ``jax.extend.core.Jaxpr``; pass ``closed.jaxpr``).

    Linear-scan liveness: inputs and constants are live at entry, each
    equation allocates its outputs, and a buffer is freed after its
    last use (jaxpr outputs live to the end).  Call-like equations
    (pjit, scan, remat, shard_map — anything carrying a sub-jaxpr)
    contribute their own internal peak *in excess of* their
    inputs+outputs while they execute.  This deliberately ignores
    XLA's rematerialization and fusion (which only shrink the true
    peak by eliding temporaries) — it is an upper-bound-flavored
    estimate whose *drift* is the signal, which is why the CLI gates
    it against the committed baseline instead of an absolute number.
    """
    return _peak(jaxpr, {})


def _peak(jaxpr, memo: Dict[int, int]) -> int:
    cached = memo.get(id(jaxpr))
    if cached is not None:
        return cached
    import jax

    core = _core()
    last_use: Dict[Any, int] = {}
    for idx, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, core.Var):
                last_use[v] = idx
    outset = {v for v in jaxpr.outvars if isinstance(v, core.Var)}
    roots = [v for v in list(jaxpr.constvars) + list(jaxpr.invars)]
    live = sum(_aval_bytes(v.aval) for v in roots)
    peak = live
    # inputs never read (donated pass-throughs aside) die immediately
    for v in roots:
        if v not in last_use and v not in outset:
            live -= _aval_bytes(v.aval)
    for idx, eqn in enumerate(jaxpr.eqns):
        # A drop-var is never written.  jax 0.9.0's backward shard_map
        # carries one per residual input (that input's cotangent):
        # pricing them as allocated-then-freed doubled the peak of the
        # data- and expert-parallel train steps.
        alloc = sum(_aval_bytes(o.aval) for o in eqn.outvars
                    if not isinstance(o, jax.core.DropVar))
        inner_excess = 0
        for sub in _sub_jaxprs(eqn):
            io = sum(_aval_bytes(v.aval)
                     for v in list(sub.invars) + list(sub.outvars))
            inner_excess = max(inner_excess,
                               max(0, _peak(sub, memo) - io))
        live += alloc
        peak = max(peak, live + inner_excess)
        for v in {v for v in eqn.invars if isinstance(v, core.Var)}:
            if last_use.get(v) == idx and v not in outset:
                live -= _aval_bytes(v.aval)
    memo[id(jaxpr)] = peak
    return peak


# ---------------------------------------------------------------------------
# APX601: donation ground truth from the lowered module
# ---------------------------------------------------------------------------

def _donated_args(stablehlo_text: str) -> Dict[int, int]:
    """{flat input index: aliased output index} parsed from the lowered
    module's argument attributes — the exact annotations XLA's
    buffer-donation pass consumes.  Single-device lowerings resolve the
    alias eagerly (``tf.aliasing_output = K``); SPMD lowerings defer the
    pairing to the compiler and mark ``jax.buffer_donor = true``
    (recorded here as output index ``-1``)."""
    start = stablehlo_text.find("@main(")
    if start < 0:
        return {}
    # walk to the close of the argument list by paren depth — arg
    # attribute dicts ({tf.aliasing_output = 0 : i32}) and loc(...)
    # annotations sit inside it, so a naive delimiter search truncates
    pos = start + len("@main(")
    depth = 1
    while pos < len(stablehlo_text) and depth:
        c = stablehlo_text[pos]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        pos += 1
    sig = stablehlo_text[start:pos]
    out: Dict[int, int] = {}
    for m in re.finditer(
            r"tf\.aliasing_output\s*=\s*(\d+)"
            r"|jax\.buffer_donor\s*=\s*true", sig):
        args_before = re.findall(r"%arg(\d+)", sig[: m.start()])
        if args_before:
            out[int(args_before[-1])] = (int(m.group(1))
                                         if m.group(1) is not None
                                         else -1)
    return out


def _arg_leaf_ranges(args: Sequence[Any]) -> List[Tuple[int, int]]:
    """[start, end) flat-leaf index range of each top-level positional
    argument — the lowered module's %argN order is the flattened
    pytree-leaf order of the call."""
    import jax

    ranges = []
    pos = 0
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        ranges.append((pos, pos + n))
        pos += n
    return ranges


# ---------------------------------------------------------------------------
# the per-entry audit
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective equation in an entry's lowered jaxpr."""

    kind: str
    elements: int        # per execution of the op
    bytes: int           # elements * itemsize * trip multiplier
    count: int           # trip multiplier (scan bodies > 1)
    path: str
    line: int
    function: str


@dataclasses.dataclass
class EntryAudit:
    """Everything the auditor measured for one entry point."""

    name: str
    collectives: List[CollectiveOp]
    peak_live_bytes: int
    donated: Dict[int, int]            # flat arg index -> output index
    findings: List[Finding]            # APX601/602/604 (baseline-free)

    def census(self) -> Dict[str, Dict[str, int]]:
        """Aggregate: kind -> {count, bytes_per_step}."""
        agg: Dict[str, Dict[str, int]] = {}
        for op in self.collectives:
            row = agg.setdefault(op.kind, {"count": 0,
                                           "bytes_per_step": 0})
            row["count"] += op.count
            row["bytes_per_step"] += op.bytes
        return agg

    def baseline_row(self) -> Dict[str, Any]:
        return {"collectives": self.census(),
                "peak_live_bytes": int(self.peak_live_bytes),
                "donated_args": sorted(self.donated)}


def _audit_one(name: str, ep, repo_root: Path) -> EntryAudit:
    import jax

    fn, args = ep.build()
    closed = jax.make_jaxpr(fn)(*args)
    lowered_text = fn.lower(*args).as_text()
    donated = _donated_args(lowered_text)
    findings: List[Finding] = []

    # --- collective census + promotions + host transfers ------------------
    collectives: List[CollectiveOp] = []
    allow = tuple(ep.allow_upcast)
    if ep.policy in ("O4", "O5", "Q8"):
        from ..testing.entry_points import POLICY_FP32_REGIONS

        allow = allow + POLICY_FP32_REGIONS
    # APX606's allow list is deliberately NOT the fp32-region list:
    # those sanction ACTIVATION upcasts (softmax, layer-norm stats);
    # an int8 WEIGHT dequant is only ever legal inside the kernels
    q8_allow = tuple(ep.allow_upcast) + Q8_DEQUANT_REGIONS
    for eqn, mult in _iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMS:
            path, line, func = _provenance(eqn, repo_root)
            nbytes = sum(_aval_bytes(o.aval) for o in eqn.outvars)
            nelems = sum(int(getattr(o.aval, "size", 0))
                         for o in eqn.outvars)
            collectives.append(CollectiveOp(
                kind=prim, elements=nelems, bytes=nbytes * mult,
                count=mult, path=path, line=line, function=func))
        elif prim == "convert_element_type" \
                and ep.policy in ("O4", "O5", "Q8"):
            src = getattr(eqn.invars[0].aval, "dtype", None)
            dst = eqn.params.get("new_dtype")
            if src is not None and str(src) in _LOWP \
                    and str(dst) == "float32":
                path, line, func = _provenance(eqn, repo_root)
                if not any(a in path for a in allow):
                    findings.append(Finding(
                        path=path, line=line, col=0, rule="APX602",
                        severity="error",
                        message=f"[{name}] silent {src}->float32 "
                                f"promotion in '{func}' — an upcast "
                                f"the {ep.policy} policy did not ask "
                                f"for (sanction the region in the "
                                f"entry registry or keep the math in "
                                f"{src})",
                        symbol=f"{name}.{func}.{src}"))
            if ep.policy == "Q8" and src is not None \
                    and str(src) == "int8" \
                    and str(dst) in ("float32", "bfloat16") \
                    and _aval_bytes(eqn.outvars[0].aval) \
                    >= _DEQUANT_MIN_BYTES:
                path, line, func = _provenance(eqn, repo_root)
                if not any(a in path for a in q8_allow):
                    findings.append(Finding(
                        path=path, line=line, col=0, rule="APX606",
                        severity="error",
                        message=f"[{name}] dequantized int8 weight "
                                f"resident: int8->{dst} of "
                                f"{_aval_bytes(eqn.outvars[0].aval)} "
                                f"bytes in '{func}' escapes the "
                                f"kernel into the compiled graph — "
                                f"Q8's contract is tile-local dequant "
                                f"(ops/quant_matmul.py); a dense "
                                f"float copy forfeits the bandwidth "
                                f"win quantization bought",
                        symbol=f"{name}.{func}.int8"))
        elif prim in HOST_TRANSFER_PRIMS:
            path, line, func = _provenance(eqn, repo_root)
            findings.append(Finding(
                path=path, line=line, col=0, rule="APX604",
                severity="error",
                message=f"[{name}] {prim} compiled into the graph in "
                        f"'{func}': XLA will round-trip the host on "
                        f"every step — the runtime transfer guard "
                        f"only catches this after deployment",
                symbol=f"{name}.{func}.{prim}"))

    # --- donation audit ----------------------------------------------------
    in_avals = list(closed.in_avals)
    out_avals = list(closed.out_avals)
    leaf_ranges = _arg_leaf_ranges(args)
    dead_leaves = set()
    for argnum in ep.dead_args:
        lo, hi = leaf_ranges[argnum]
        dead_leaves.update(range(lo, hi))
    # outputs already claimed by an existing alias are off the table
    free_outputs: Dict[Tuple[Any, Any], int] = {}
    claimed = {v for v in donated.values() if v >= 0}
    for i, aval in enumerate(out_avals):
        if i in claimed:
            continue
        key = (getattr(aval, "shape", None), getattr(aval, "dtype", None))
        free_outputs[key] = free_outputs.get(key, 0) + 1
    missed: Dict[int, Tuple[int, int]] = {}  # argnum -> (leaves, bytes)
    for leaf in sorted(dead_leaves):
        if leaf in donated or leaf >= len(in_avals):
            continue
        aval = in_avals[leaf]
        if _aval_bytes(aval) < _DONATION_MIN_BYTES:
            continue
        key = (getattr(aval, "shape", None), getattr(aval, "dtype", None))
        if free_outputs.get(key, 0) <= 0:
            continue
        free_outputs[key] -= 1
        argnum = next(i for i, (lo, hi) in enumerate(leaf_ranges)
                      if lo <= leaf < hi)
        n, b = missed.get(argnum, (0, 0))
        missed[argnum] = (n + 1, b + _aval_bytes(aval))
    for argnum, (n, b) in sorted(missed.items()):
        findings.append(Finding(
            path=f"<entry:{name}>", line=0, col=0, rule="APX601",
            severity="error",
            message=f"[{name}] arg {argnum} is dead after the call "
                    f"with {n} buffer(s) / {b} bytes matching "
                    f"undonated outputs — add it to donate_argnums "
                    f"(masters/optimizer state must be donated "
                    f"end-to-end)",
            symbol=f"arg{argnum}"))

    return EntryAudit(name=name, collectives=collectives,
                      peak_live_bytes=peak_live_bytes(closed.jaxpr),
                      donated=donated, findings=findings)


def audit_entry_points(repo_root: str = ".",
                       names: Optional[Sequence[str]] = None
                       ) -> Dict[str, EntryAudit]:
    """Audit every registered entry point buildable on this host."""
    from ..testing.entry_points import available_entry_points

    root = Path(repo_root).resolve()
    audits = {}
    for name, ep in available_entry_points().items():
        if names is not None and name not in names:
            continue
        audits[name] = _audit_one(name, ep, root)
    return audits


# ---------------------------------------------------------------------------
# baseline diff (APX603 / APX605) and the check entry
# ---------------------------------------------------------------------------

def load_hlo_baseline(path: str = DEFAULT_HLO_BASELINE, *,
                      repo_root: str = ".") -> Dict[str, Any]:
    p = Path(repo_root) / path
    if not p.exists():
        return {"entries": {}}
    return json.loads(p.read_text())


def write_hlo_baseline(audits: Dict[str, EntryAudit],
                       path: str = DEFAULT_HLO_BASELINE, *,
                       repo_root: str = ".") -> None:
    """Rewrite the census/memory baseline: audited entries get fresh
    rows, entries NOT audited this run (``--entry`` filter, or a host
    without the multichip device count) keep their committed rows —
    a partial update must never silently delete the rest of the
    baseline.  Rows for entry points that no longer exist are the one
    thing dropped (that is the stale cleanup --update exists for)."""
    import jax

    from ..testing.entry_points import ENTRY_POINTS

    existing = load_hlo_baseline(path, repo_root=repo_root).get(
        "entries", {})
    rows = {name: row for name, row in existing.items()
            if name in ENTRY_POINTS}
    rows.update({name: a.baseline_row() for name, a in audits.items()})
    payload = {
        "_comment": [
            "Committed collective-census + peak-live-memory baseline",
            "for the registered entry points "
            "(apex_tpu/testing/entry_points.py).",
            "Regenerate with: python -m apex_tpu.analysis "
            "--update-hlo-baseline",
            "(CPU lowerings, 8 host-platform devices — the tools/"
            "ci.sh step 7 configuration).",
            "APX603/APX605 gate every entry against these rows at "
            "+/-10%.",
        ],
        "jax_version": jax.__version__,
        "entries": {name: rows[name] for name in sorted(rows)},
    }
    (Path(repo_root) / path).write_text(
        json.dumps(payload, indent=1, sort_keys=True) + "\n")


def _census_findings(name: str, audit: EntryAudit,
                     base_row: Optional[Dict[str, Any]]
                     ) -> List[Finding]:
    out: List[Finding] = []

    def emit(rule: str, symbol: str, message: str) -> None:
        out.append(Finding(path=f"<entry:{name}>", line=0, col=0,
                           rule=rule, severity="error",
                           message=f"[{name}] {message}",
                           symbol=symbol))

    if base_row is None:
        emit("APX603", "unbaselined",
             "entry point has no committed census row — run "
             "'python -m apex_tpu.analysis --update-hlo-baseline' and "
             "review the diff")
        return out
    census = audit.census()
    base_cens = base_row.get("collectives", {})
    for kind, row in sorted(census.items()):
        ops = [op for op in audit.collectives if op.kind == kind]
        where = "; ".join(
            f"{op.path}:{op.line} in {op.function}"
            f"{f' x{op.count}' if op.count > 1 else ''}"
            for op in ops[:4])
        b = base_cens.get(kind)
        if b is None:
            emit("APX603", f"{kind}.new",
                 f"NEW collective kind '{kind}': {row['count']} op(s), "
                 f"{row['bytes_per_step']} bytes/step — emitted at "
                 f"{where}")
            continue
        if row["count"] > b["count"]:
            emit("APX603", f"{kind}.count",
                 f"collective '{kind}' count grew "
                 f"{b['count']} -> {row['count']} — new op(s) at "
                 f"{where}")
        elif row["count"] < b["count"]:
            emit("APX603", f"{kind}.count",
                 f"collective '{kind}' count shrank "
                 f"{b['count']} -> {row['count']} — refresh the "
                 f"baseline (--update-hlo-baseline) so the gate "
                 f"tracks the improvement")
        hi = b["bytes_per_step"] * (1 + _GROWTH_TOL)
        lo = b["bytes_per_step"] * (1 - _GROWTH_TOL)
        if row["bytes_per_step"] > hi:
            emit("APX603", f"{kind}.bytes",
                 f"collective '{kind}' bytes/step grew >10%: "
                 f"{b['bytes_per_step']} -> {row['bytes_per_step']} — "
                 f"ops at {where}")
        elif row["bytes_per_step"] < lo:
            emit("APX603", f"{kind}.bytes",
                 f"collective '{kind}' bytes/step shrank >10% "
                 f"({b['bytes_per_step']} -> {row['bytes_per_step']}) "
                 f"— refresh the baseline to lock in the win")
    for kind in sorted(set(base_cens) - set(census)):
        emit("APX603", f"{kind}.gone",
             f"baselined collective kind '{kind}' no longer emitted — "
             f"refresh the baseline")
    base_peak = base_row.get("peak_live_bytes", 0)
    peak = audit.peak_live_bytes
    if peak > base_peak * (1 + _GROWTH_TOL):
        emit("APX605", "peak",
             f"peak-live-memory estimate grew >10%: {base_peak} -> "
             f"{peak} bytes")
    elif peak < base_peak * (1 - _GROWTH_TOL):
        emit("APX605", "peak",
             f"peak-live-memory estimate shrank >10% ({base_peak} -> "
             f"{peak} bytes) — refresh the baseline to lock in the "
             f"win")
    return out


def run_hlo_check(repo_root: str = ".", *,
                  baseline: str = DEFAULT_HLO_BASELINE,
                  findings_baseline: str = DEFAULT_HLO_FINDINGS,
                  names: Optional[Sequence[str]] = None
                  ) -> Tuple[List[Finding], List[str],
                             Dict[str, EntryAudit]]:
    """The ``--check-hlo`` engine.

    Returns ``(unsuppressed findings, stale suppression keys, audits)``
    — non-empty findings or stale keys mean a red build.  Entries the
    host cannot build (device-count gate) are skipped without touching
    their baseline rows, so a single-device invocation never reports
    the multichip rows stale.
    """
    from ..testing.entry_points import ENTRY_POINTS

    audits = audit_entry_points(repo_root, names=names)
    base = load_hlo_baseline(baseline, repo_root=repo_root)
    entries = base.get("entries", {})
    findings: List[Finding] = []
    for name, audit in sorted(audits.items()):
        findings.extend(audit.findings)
        findings.extend(_census_findings(name, audit,
                                         entries.get(name)))
    # baseline rows for entry points that no longer exist at all are
    # stale (rows for merely-unbuildable entries are fine)
    for name in sorted(set(entries) - set(ENTRY_POINTS)):
        findings.append(Finding(
            path=f"<entry:{name}>", line=0, col=0, rule="APX603",
            severity="error",
            message=f"[{name}] baseline row for an entry point that "
                    f"is no longer registered — delete it "
                    f"(--update-hlo-baseline)",
            symbol="stale-entry"))
    suppress = load_baseline(findings_baseline, repo_root=repo_root)
    live_keys = {f.key for f in findings}
    unsuppressed = [f for f in findings if f.key not in suppress]
    # a suppression is stale only when the entry it belongs to was
    # actually audited this run: a device-gated or --entry-filtered
    # invocation must not demand deletion of a line the full CI run
    # still needs (mirror of the baseline-row rule above)
    full_run = set(audits) == set(ENTRY_POINTS)

    def checked_this_run(key: str) -> bool:
        owner = _suppression_entry(key)
        if owner in audits:
            return True
        # unattributable keys, and keys for entries that no longer
        # exist, can only be judged by a full run
        return full_run and (owner is None or owner not in ENTRY_POINTS)

    stale = [k for k in suppress
             if k not in live_keys and checked_this_run(k)]
    return unsuppressed, stale, audits


def _suppression_entry(key: str) -> Optional[str]:
    """Best-effort owning entry point of a suppression key.  APX601/
    603/605 keys carry it in the ``<entry:NAME>`` pseudo-path;
    APX602/604 keys carry it as the symbol's first dotted component
    (``{entry}.{function}.{detail}``)."""
    path = key.split(":", 1)[0]
    if path.startswith("<entry:") and path.endswith(">"):
        return path[len("<entry:"):-1]
    sym = key.rsplit(":", 1)[-1]
    if "." in sym:
        return sym.split(".", 1)[0]
    return None
