"""SPMD sharding auditor: declared vs propagated sharding, reshard
chains, overlap preconditions, and per-device memory over the
partitioned entry points.

PR 6's compiled-graph auditor (:mod:`.hlo`) proves donation, promotion,
and the collective census on the *logical* graph; this module audits
the **partitioned** artifact: every multichip entry in
:mod:`apex_tpu.testing.entry_points` that carries a
:class:`apex_tpu.mesh_plan.MeshPlan` is lowered AND compiled under its
mesh, and the partitioner's actual output — propagated argument/result
shardings, per-device memory, the collective schedule — is checked
against the plan.  Declared partitioning is a contract; a silently
replicated ZeRO shard or an accidental all-gather→reduce-scatter
round-trip is invisible at the source layer and only shows up as a TPU
bill at runtime.  Here it fails CI.

Rules (registered in :mod:`.rules`, table in docs/api/analysis.md):

* **APX701 unintended full replication** — a tensor above the
  ``APEX_TPU_SHARDING_MIN_BYTES`` floor whose plan spec shards it over
  an axis, but whose propagated sharding is fully replicated: the
  classic silent-ZeRO-regression (every device pays full-state memory
  while the plan promised 1/N).
* **APX702 reshard chain** — an ``all_gather`` whose result feeds a
  ``reduce_scatter`` or a ``dynamic_slice`` re-partition of the same
  operand (directly or through elementwise converts): the bytes were
  gathered only to be thrown away, with both ops' jaxpr provenance.
* **APX703 declared-vs-propagated drift** — a plan-declared spec the
  partitioner resolved differently (neither matching nor replicated —
  that case is APX701), a declared pattern matching no tensor (stale
  plan), or a collective-budget overrun / unbudgeted collective kind
  (census from the jaxpr, scan bodies priced by trip count, with the
  innermost repo frame named).
* **APX704 non-overlappable collective** *(advisory)* — an
  all_to_all / all_gather whose first consumer is the immediately
  following equation while later equations independent of it exist:
  the MoE a2a/expert-compute overlap precondition is not met as
  written, so the scheduler has nothing to hide the transfer behind.
  Advisory: printed, never red.
* **APX705 per-device peak-memory drift** — XLA's own per-device
  memory analysis of the partitioned executable (arguments + outputs +
  temps − donation-aliased), gated ±10% against the committed
  ``tools/sharding_baseline.json`` row per entry/topology.

The baseline file also commits each entry's plan (axes, sizes, kinds,
budget) — a topology change is a reviewed JSON diff, not a silent code
path.  APX701–703 findings suppress through
``tools/sharding_findings.txt`` (the PR-5 reasoned-baseline machinery;
committed EMPTY — the real finding at introduction, the ZeRO bench
driver's replicated state boundary, was FIXED).  CLI:
``python -m apex_tpu.analysis --check-sharding`` /
``--update-sharding-baseline`` (tools/ci.sh step 11, CPU lowerings on
the 8-device host-platform mesh).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .hlo import (COLLECTIVE_PRIMS, _aval_bytes, _iter_eqns,
                  _provenance, _sub_jaxprs)
from .linter import Finding, load_baseline

__all__ = ["ShardingAudit", "audit_sharding", "run_sharding_check",
           "write_sharding_baseline", "DEFAULT_SHARDING_BASELINE",
           "DEFAULT_SHARDING_FINDINGS", "tensor_paths"]

DEFAULT_SHARDING_BASELINE = "tools/sharding_baseline.json"
DEFAULT_SHARDING_FINDINGS = "tools/sharding_findings.txt"

_MEM_TOL = 0.10  # APX705 gate, both directions (the drift is the signal)

# prims a gathered value may pass through and still count as "the same
# operand" for the APX702 chain walk
_PASSTHROUGH_PRIMS = {"convert_element_type", "copy"}
# consumers that re-partition a gathered operand
_REPARTITION_PRIMS = {"reduce_scatter", "dynamic_slice"}
# collectives whose latency wants hiding behind independent compute
_OVERLAP_PRIMS = {"all_to_all", "all_gather"}


def _min_bytes() -> int:
    from .flags import flag_int

    return flag_int("APEX_TPU_SHARDING_MIN_BYTES")


# ---------------------------------------------------------------------------
# tensor naming: flat leaves -> stable audit paths
# ---------------------------------------------------------------------------

def tensor_paths(tree: Any, prefix: str) -> List[str]:
    """One stable path string per flat leaf of ``tree``:
    ``in0['params']['w']``, ``out1.m[0]`` — what plan patterns match
    against.  Ordering == ``jax.tree_util.tree_leaves`` order (the
    lowering's flat argument order)."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [prefix + jax.tree_util.keystr(path) for path, _ in leaves]


def _arg_paths(args: Sequence[Any]) -> List[str]:
    out: List[str] = []
    for i, a in enumerate(args):
        out.extend(tensor_paths(a, f"in{i}"))
    return out


def _flatten_shardings(shardings: Any) -> List[Any]:
    import jax

    return jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda s: hasattr(s, "shard_shape"))


# ---------------------------------------------------------------------------
# the per-entry audit
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardingAudit:
    """Everything the SPMD auditor measured for one planned entry."""

    name: str
    plan_json: Dict[str, Any]
    per_device_bytes: Optional[int]     # None when XLA reports nothing
    census: Dict[str, int]              # collective kind -> ops/step
    findings: List[Finding]             # APX701/702/703 (errors)
    advisories: List[Finding]           # APX704 (never red)

    def baseline_row(self) -> Dict[str, Any]:
        return {"plan": self.plan_json,
                "per_device_bytes": self.per_device_bytes,
                "collectives": dict(sorted(self.census.items()))}


def _spec_findings(entry: str, plan, paths: List[str],
                   shardings: List[Any], avals: List[Any],
                   repo_root: Path, *,
                   check_stale: bool = True) -> List[Finding]:
    """APX701/APX703 over one flat (path, sharding, aval) list.
    ``check_stale=False`` skips the pattern-matches-nothing rule —
    used when part of the path universe was dropped (misaligned
    flattening), where 'stale' would be a false accusation."""
    findings: List[Finding] = []
    floor = _min_bytes()
    matched_patterns = set()
    for path, sh, aval in zip(paths, shardings, avals):
        spec = plan.spec_for(path)
        if spec is None:
            continue
        matched_patterns.add(_pattern_of(plan, path))
        shape = tuple(getattr(aval, "shape", ()))
        nbytes = _aval_bytes(aval)
        try:
            want = plan.expected_shard_shape(shape, spec)
        except ValueError as e:
            findings.append(Finding(
                path=f"<entry:{entry}>", line=0, col=0, rule="APX703",
                severity="error",
                message=f"[{entry}] plan spec for {path} does not fit "
                        f"its shape: {e}",
                symbol=f"{entry}.spec.{_sym(path)}"))
            continue
        if sh is None:
            continue
        have = tuple(sh.shard_shape(shape))
        if have == want:
            continue
        if have == shape and want != shape:
            if nbytes < floor:
                continue  # replicating a scalar costs nothing
            # fully replicated where the plan shards: the silent-ZeRO
            # regression — every device pays sharded_factor x the
            # memory the plan promised
            findings.append(Finding(
                path=f"<entry:{entry}>", line=0, col=0, rule="APX701",
                severity="error",
                message=f"[{entry}] {path} ({nbytes} bytes) is fully "
                        f"REPLICATED but the plan shards it {spec} — "
                        f"per-device cost is the whole tensor, not "
                        f"{want}; the partitioner never saw the "
                        f"declared sharding (check the shard_map "
                        f"in/out_specs or in_shardings derive from "
                        f"the plan)",
                symbol=f"{entry}.replicated.{_sym(path)}"))
        else:
            findings.append(Finding(
                path=f"<entry:{entry}>", line=0, col=0, rule="APX703",
                severity="error",
                message=f"[{entry}] {path}: plan declares {spec} "
                        f"(per-device {want}) but the partitioner "
                        f"assigned per-device {have} of global "
                        f"{shape}",
                symbol=f"{entry}.drift.{_sym(path)}"))
    # a declared pattern matching NO tensor is a stale plan — the
    # contract must track reality or it checks nothing
    for pattern, _ in plan.tensor_specs if check_stale else ():
        if pattern in matched_patterns:
            continue
        if not any(_re_search(pattern, p) for p in paths):
            findings.append(Finding(
                path=f"<entry:{entry}>", line=0, col=0, rule="APX703",
                severity="error",
                message=f"[{entry}] plan pattern {pattern!r} matches "
                        f"no audited tensor — stale spec (update the "
                        f"plan with the entry)",
                symbol=f"{entry}.stale-pattern.{_sym(pattern)}"))
    return findings


def _re_search(pattern: str, path: str) -> bool:
    import re

    return re.search(pattern, path) is not None


def _pattern_of(plan, path: str) -> Optional[str]:
    for pattern, _ in plan.tensor_specs:
        if _re_search(pattern, path):
            return pattern
    return None


def _sym(path: str) -> str:
    """Stable, baseline-friendly symbol from an audit path."""
    return "".join(c if c.isalnum() or c in "._" else "-"
                   for c in path)


def _chain_findings(entry: str, jaxpr, repo_root: Path
                    ) -> Tuple[List[Finding], List[Finding]]:
    """APX702 (reshard chains) + APX704 (overlap advisories) over one
    jaxpr and its sub-jaxprs.  Each (sub-)jaxpr is walked linearly in
    trace order — the order XLA schedules absent other constraints."""
    core_mod = _jax_core()
    errors: List[Finding] = []
    advisories: List[Finding] = []

    def walk(jx):
        # var -> provenance of the all_gather that produced it (chased
        # through pass-through prims)
        gathered: Dict[Any, Tuple[str, int, str]] = {}
        eqns = list(jx.eqns)
        for idx, eqn in enumerate(eqns):
            prim = eqn.primitive.name
            invars = [v for v in eqn.invars
                      if isinstance(v, core_mod.Var)]
            if prim in _REPARTITION_PRIMS:
                for v in invars:
                    src = gathered.get(v)
                    if src is None:
                        continue
                    spath, sline, sfunc = src
                    path, line, func = _provenance(eqn, repo_root)
                    errors.append(Finding(
                        path=spath, line=sline, col=0, rule="APX702",
                        severity="error",
                        message=f"[{entry}] all_gather at "
                                f"{spath}:{sline} in '{sfunc}' feeds a "
                                f"{prim} re-partition of the same "
                                f"operand at {path}:{line} in "
                                f"'{func}' — the gathered bytes are "
                                f"immediately thrown away (keep the "
                                f"shard, or fuse the pair into the "
                                f"collective that says what you "
                                f"mean)",
                        symbol=f"{entry}.{sfunc}.{prim}"))
            if prim == "all_gather":
                for o in eqn.outvars:
                    gathered[o] = _provenance(eqn, repo_root)
            elif prim in _PASSTHROUGH_PRIMS and invars:
                src = gathered.get(invars[0])
                if src is not None:
                    for o in eqn.outvars:
                        gathered[o] = src
            if prim in _OVERLAP_PRIMS:
                adv = _overlap_advisory(entry, eqns, idx, core_mod,
                                        repo_root)
                if adv is not None:
                    advisories.append(adv)
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return errors, advisories


def _overlap_advisory(entry: str, eqns, idx, core_mod,
                      repo_root: Path) -> Optional[Finding]:
    """APX704: the collective at ``eqns[idx]`` is non-overlappable as
    written when (a) the IMMEDIATELY next equation consumes its output
    (the schedule has zero slack), and (b) some later equation in the
    same jaxpr is independent of it (work existed that could have been
    hoisted in between).  A linear-order approximation on purpose:
    XLA may still reorder, but the trace order is what the author
    wrote, and the MoE overlap literature is about restructuring
    exactly this."""
    eqn = eqns[idx]
    outs = set(eqn.outvars)
    if idx + 1 >= len(eqns):
        return None
    nxt = eqns[idx + 1]
    nxt_in = {v for v in nxt.invars if isinstance(v, core_mod.Var)}
    if not (outs & nxt_in):
        return None  # slack already exists
    # transitively taint everything dependent on the collective; an
    # untainted later equation with real output bytes is independent
    # compute that could overlap the transfer
    tainted = set(outs)
    independent = None
    for later in eqns[idx + 1:]:
        lin = {v for v in later.invars if isinstance(v, core_mod.Var)}
        if lin & tainted:
            tainted.update(later.outvars)
            continue
        if later.primitive.name in COLLECTIVE_PRIMS:
            continue
        if sum(_aval_bytes(o.aval) for o in later.outvars) > 0:
            independent = later
            break
    if independent is None:
        return None
    path, line, func = _provenance(eqn, repo_root)
    ipath, iline, ifunc = _provenance(independent, repo_root)
    return Finding(
        path=path, line=line, col=0, rule="APX704",
        severity="advisory",
        message=f"[{entry}] {eqn.primitive.name} at {path}:{line} in "
                f"'{func}' is consumed by the immediately following "
                f"equation while independent compute exists later "
                f"({independent.primitive.name} at {ipath}:{iline} in "
                f"'{ifunc}') — reorder so the transfer overlaps it "
                f"(the MoE a2a/expert-compute precondition)",
        symbol=f"{entry}.{func}.{eqn.primitive.name}")


def _jax_core():
    import jax.extend.core

    return jax.extend.core


def _collective_census(jaxpr) -> Tuple[Dict[str, int],
                                       Dict[str, List[Any]]]:
    """kind -> ops/step (scan-multiplied), plus the eqns per kind for
    budget-overrun provenance."""
    census: Dict[str, int] = {}
    ops: Dict[str, List[Any]] = {}
    for eqn, mult in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMS:
            census[prim] = census.get(prim, 0) + mult
            ops.setdefault(prim, []).append(eqn)
    return census, ops


def _budget_findings(entry: str, plan, census: Dict[str, int],
                     ops: Dict[str, List[Any]], repo_root: Path
                     ) -> List[Finding]:
    budget = plan.budget()
    if not budget:
        return []  # a plan may decline to budget (specs-only contract)
    findings: List[Finding] = []
    for kind, count in sorted(census.items()):
        where = "; ".join(
            "{}:{} in {}".format(*_provenance(e, repo_root))
            for e in ops.get(kind, [])[:4])
        if kind not in budget:
            findings.append(Finding(
                path=f"<entry:{entry}>", line=0, col=0, rule="APX703",
                severity="error",
                message=f"[{entry}] UNBUDGETED collective kind "
                        f"'{kind}' ({count} op(s)/step) — the plan's "
                        f"budget {budget} does not mention it; emitted "
                        f"at {where}",
                symbol=f"{entry}.budget.{kind}.unbudgeted"))
        elif count > budget[kind]:
            findings.append(Finding(
                path=f"<entry:{entry}>", line=0, col=0, rule="APX703",
                severity="error",
                message=f"[{entry}] collective '{kind}' exceeds the "
                        f"plan budget: {count} op(s)/step > "
                        f"{budget[kind]} budgeted; emitted at {where}",
                symbol=f"{entry}.budget.{kind}.over"))
    return findings


def _per_device_bytes(compiled) -> Optional[int]:
    """XLA's own per-device footprint of the partitioned executable:
    arguments + outputs + temps, minus donation-aliased bytes (those
    buffers are reused, not re-allocated).  None when the backend
    reports nothing — an honest skip, never a zero."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # apex-lint: disable=APX202 -- backend-optional API: absence degrades to an honest null, not a crash
        return None
    if ma is None:
        return None
    total = 0
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
        total += int(getattr(ma, field, 0) or 0)
    total -= int(getattr(ma, "alias_size_in_bytes", 0) or 0)
    return total if total > 0 else None


def _audit_one(name: str, ep, repo_root: Path) -> ShardingAudit:
    import jax

    plan = ep.plan()
    fn, args = ep.build()
    closed = jax.make_jaxpr(fn)(*args)
    compiled = fn.lower(*args).compile()

    findings: List[Finding] = []

    # --- declared vs propagated shardings (APX701/703) --------------------
    in_paths = _arg_paths(args)
    in_shardings = _flatten_shardings(compiled.input_shardings[0])
    in_avals = list(closed.in_avals)
    out_shardings = _flatten_shardings(compiled.output_shardings)
    out_avals = list(closed.out_avals)
    # output paths from the avals' positional structure alone (the
    # output pytree is not observable without executing) — plans name
    # outputs by flat position: out0, out1, ...
    out_paths = [f"out{i}" for i in range(len(out_avals))]
    paths, shardings, avals = [], [], []
    for kind, p, s, a in (("input", in_paths, in_shardings, in_avals),
                          ("output", out_paths, out_shardings,
                           out_avals)):
        if len(p) == len(s) == len(a):
            paths += p
            shardings += s
            avals += a
        else:
            # never mis-zip paths/shardings/avals: a backend that
            # flattens differently gets ONE honest loud finding, not
            # a wall of bogus drift/stale-spec errors from shifted
            # pairings
            findings.append(Finding(
                path=f"<entry:{name}>", line=0, col=0, rule="APX703",
                severity="error",
                message=f"[{name}] auditor could not align {kind} "
                        f"paths/shardings/avals "
                        f"({len(p)}/{len(s)}/{len(a)} leaves) — the "
                        f"backend flattened the {kind}s differently; "
                        f"{kind} spec checks skipped this run",
                symbol=f"{name}.misaligned.{kind}"))
    aligned = len(paths) == len(in_paths) + len(out_paths)
    findings.extend(_spec_findings(name, plan, paths, shardings,
                                   avals, repo_root,
                                   check_stale=aligned))

    # --- reshard chains + overlap advisories (APX702/704) ------------------
    errors, advisories = _chain_findings(name, closed.jaxpr, repo_root)
    findings.extend(errors)

    # --- collective budget (APX703) ----------------------------------------
    census, ops = _collective_census(closed.jaxpr)
    findings.extend(_budget_findings(name, plan, census, ops,
                                     repo_root))

    return ShardingAudit(
        name=name, plan_json=plan.to_json(),
        per_device_bytes=_per_device_bytes(compiled),
        census=census, findings=findings, advisories=advisories)


def audit_sharding(repo_root: str = ".",
                   names: Optional[Sequence[str]] = None
                   ) -> Dict[str, ShardingAudit]:
    """Audit every buildable entry point that carries a MeshPlan."""
    from ..testing.entry_points import available_entry_points

    root = Path(repo_root).resolve()
    audits = {}
    for name, ep in available_entry_points().items():
        if ep.plan is None:
            continue
        if names is not None and name not in names:
            continue
        audits[name] = _audit_one(name, ep, root)
    return audits


# ---------------------------------------------------------------------------
# baseline (plan + per-device memory) and the check entry
# ---------------------------------------------------------------------------

def load_sharding_baseline(path: str = DEFAULT_SHARDING_BASELINE, *,
                           repo_root: str = ".") -> Dict[str, Any]:
    p = Path(repo_root) / path
    if not p.exists():
        return {"entries": {}}
    return json.loads(p.read_text())


def write_sharding_baseline(audits: Dict[str, ShardingAudit],
                            path: str = DEFAULT_SHARDING_BASELINE, *,
                            repo_root: str = ".") -> None:
    """Rewrite the committed topology/memory baseline.  Same partial-
    update contract as the hlo baseline: entries not audited this run
    keep their rows; rows for unregistered entries are dropped."""
    import jax

    from ..testing.entry_points import ENTRY_POINTS

    existing = load_sharding_baseline(path, repo_root=repo_root).get(
        "entries", {})
    rows = {name: row for name, row in existing.items()
            if name in ENTRY_POINTS}
    rows.update({name: a.baseline_row() for name, a in audits.items()})
    payload = {
        "_comment": [
            "Committed MeshPlan topology + per-device memory baseline",
            "for the planned entry points "
            "(apex_tpu/testing/entry_points.py).",
            "Regenerate with: python -m apex_tpu.analysis "
            "--update-sharding-baseline",
            "(CPU lowerings, 8 host-platform devices — the tools/"
            "ci.sh step 11 configuration).",
            "A plan diff here IS the topology review; APX705 gates "
            "per_device_bytes at +/-10%.",
        ],
        "jax_version": jax.__version__,
        "entries": {name: rows[name] for name in sorted(rows)},
    }
    (Path(repo_root) / path).write_text(
        json.dumps(payload, indent=1, sort_keys=True) + "\n")


def _baseline_findings(name: str, audit: ShardingAudit,
                       base_row: Optional[Dict[str, Any]]
                       ) -> List[Finding]:
    out: List[Finding] = []

    def emit(rule: str, symbol: str, message: str) -> None:
        out.append(Finding(path=f"<entry:{name}>", line=0, col=0,
                           rule=rule, severity="error",
                           message=f"[{name}] {message}",
                           symbol=symbol))

    if base_row is None:
        emit("APX705", "unbaselined",
             "entry point has no committed sharding-baseline row — "
             "run 'python -m apex_tpu.analysis "
             "--update-sharding-baseline' and review the diff")
        return out
    if base_row.get("plan") != audit.plan_json:
        emit("APX703", "plan-drift",
             "MeshPlan changed vs the committed baseline (axes/sizes/"
             "kinds/specs/budget) — a topology change must be a "
             "reviewed baseline diff (--update-sharding-baseline)")
    base_mem = base_row.get("per_device_bytes")
    mem = audit.per_device_bytes
    if base_mem is not None and mem is not None:
        if mem > base_mem * (1 + _MEM_TOL):
            emit("APX705", "per-device-mem",
                 f"per-device memory grew >10%: {base_mem} -> {mem} "
                 f"bytes (arguments+outputs+temps per device, XLA "
                 f"memory analysis of the partitioned executable)")
        elif mem < base_mem * (1 - _MEM_TOL):
            emit("APX705", "per-device-mem",
                 f"per-device memory shrank >10% ({base_mem} -> {mem} "
                 f"bytes) — refresh the baseline to lock in the win")
    elif (base_mem is None) != (mem is None):
        emit("APX705", "per-device-mem",
             f"per-device memory availability changed "
             f"({base_mem} -> {mem}) — refresh the baseline")
    return out


def run_sharding_check(repo_root: str = ".", *,
                       baseline: str = DEFAULT_SHARDING_BASELINE,
                       findings_baseline: str = DEFAULT_SHARDING_FINDINGS,
                       names: Optional[Sequence[str]] = None
                       ) -> Tuple[List[Finding], List[Finding],
                                  List[str], Dict[str, ShardingAudit]]:
    """The ``--check-sharding`` engine.

    Returns ``(errors, advisories, stale suppression keys, audits)`` —
    non-empty errors or stale keys mean a red build; advisories
    (APX704) print but never fail.  Entries the host cannot build
    (device-count gate) skip without touching their baseline rows,
    mirroring the hlo checker's semantics.
    """
    from ..testing.entry_points import ENTRY_POINTS

    audits = audit_sharding(repo_root, names=names)
    base = load_sharding_baseline(baseline, repo_root=repo_root)
    entries = base.get("entries", {})
    findings: List[Finding] = []
    advisories: List[Finding] = []
    for name, audit in sorted(audits.items()):
        findings.extend(audit.findings)
        advisories.extend(audit.advisories)
        findings.extend(_baseline_findings(name, audit,
                                           entries.get(name)))
    planned = {n for n, ep in ENTRY_POINTS.items() if ep.plan is not None}
    for name in sorted(set(entries) - planned):
        findings.append(Finding(
            path=f"<entry:{name}>", line=0, col=0, rule="APX705",
            severity="error",
            message=f"[{name}] sharding-baseline row for an entry "
                    f"point that is no longer registered with a plan "
                    f"— delete it (--update-sharding-baseline)",
            symbol="stale-entry"))
    suppress = load_baseline(findings_baseline, repo_root=repo_root)
    live_keys = {f.key for f in findings}
    unsuppressed = [f for f in findings if f.key not in suppress]
    # staleness is only judged by a run that audited everything (the
    # hlo checker's rule): a device-gated or --entry-filtered run must
    # not demand deletion of a line full CI still needs
    full_run = names is None and set(audits) == planned

    def checked_this_run(key: str) -> bool:
        owner = _suppression_entry(key)
        if owner in audits:
            return True
        return full_run and (owner is None or owner not in ENTRY_POINTS)

    stale = [k for k in suppress
             if k not in live_keys and checked_this_run(k)]
    return unsuppressed, advisories, stale, audits


def _suppression_entry(key: str) -> Optional[str]:
    """Entry a suppression key belongs to: the ``<entry:NAME>`` path
    prefix (keys are ``<entry:NAME>:RULE:symbol`` — the path itself
    contains a colon, so match the closing ``>``), else the symbol's
    leading dotted component."""
    import re

    m = re.match(r"<entry:([^>]+)>:", key)
    if m:
        return m.group(1)
    sym = key.rsplit(":", 1)[-1]
    if "." in sym:
        return sym.split(".", 1)[0]
    return None
