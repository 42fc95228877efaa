"""Measured-profile ingestion: join device times onto the analytical map.

The reference's pyprof pipeline has two halves: ``parse`` reads the
*measured* per-kernel times out of the nvprof SQLite database
(ref: apex/pyprof/parse/nvvp.py:282 ``getKernelInfo`` joins the CUPTI
kernel table with markers) and ``prof`` attaches the analytical
flops/bytes models (ref: apex/pyprof/prof/output.py).  Round 1/2 built
the analytical half (:mod:`apex_tpu.pyprof.prof`); this module is the
measured half for TPU: it runs a function under ``jax.profiler``,
parses the xplane protobuf with xprof's ``framework_op_stats`` tool,
and JOINS measured per-op device microseconds onto the analytical
:class:`~apex_tpu.pyprof.prof.OpRecord` rows by (scope, op) name.

XLA fuses aggressively, so the join is name-canonical rather than 1:1:
measured rows carry the scope of their fusion's root op.  Rows that
match get both columns; measured rows with no analytical counterpart
(fusions, copies, infrastructure) are kept with empty analytical
columns so the TOTAL line always reconciles against the step's device
time.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re
import shutil
import tempfile
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from .prof import OpRecord, analyze, device_spec

__all__ = ["MeasuredOp", "collect_device_ops", "canonical_key",
           "join_measured", "parse_op_stats", "profile_call",
           "profile_measured", "measured_report"]


@dataclass
class MeasuredOp:
    """One device-op row from the profiler, normalized PER ITERATION
    (both fields are divided by the profiled repeat count)."""
    name: str           # full framework op name
    op_type: str        # HLO/op category reported by xprof
    occurrences: float  # executions per iteration
    total_us: float     # per-iteration device self-time


_WRAPPER = re.compile(r"^(jit|pjit|closed_call|core_call|remat\d?|"
                      r"checkpoint|named)\(.*\)$")
# bare call-primitive segments the analytical walker inserts when it
# recurses into sub-jaxprs (prof._walk appends the primitive name)
_BARE_WRAPPERS = frozenset({"jit", "pjit", "closed_call", "core_call",
                            "remat", "remat2", "checkpoint",
                            "custom_vjp_call", "custom_jvp_call"})


def canonical_key(name: str) -> Tuple[str, str]:
    """(op, scope) canonical join key for a framework-op-stats name or
    an analytical record's scope/op pair.

    Drops ``jit(...)`` wrapper segments (both the profiler's
    ``jit(fn)`` form and the walker's bare ``pjit`` segments) and
    trailing ``.N`` op-number suffixes so
    ``jit(step)/jvp(Model)/mlp/dot_general.1`` and the jaxpr walker's
    ``jvp(Model)/mlp`` + ``dot_general`` meet at
    ``("dot_general", "jvp(Model)/mlp")``."""
    parts = [p for p in name.split("/") if p]
    parts = [p for p in parts
             if not _WRAPPER.match(p) and p not in _BARE_WRAPPERS]
    if not parts:
        return name, ""
    op = re.sub(r"\.\d+$", "", parts[-1])
    return op, "/".join(parts[:-1])


def collect_device_ops(fn: Callable, *args, iters: int = 3,
                       trace_dir: Optional[str] = None,
                       donate: bool = False,
                       **kwargs) -> List[MeasuredOp]:
    """Run ``jit(fn)`` under ``jax.profiler`` and return per-op device
    self-times (the reference's parse stage; xplane instead of nvvp).

    .. warning:: Totals come back **already normalized to one
       execution of fn** (the trace sums all ``iters`` dispatches and
       this function divides by ``iters``) — do NOT divide by
       ``iters`` again.  Calibration anchor: a 4096^3 bf16 matmul
       reports the same 718 us ~ 191 TF/s at iters 1/3/6.
       Occurrences INSIDE one program (e.g. a ``lax.scan`` body) still
       sum within the execution — for a per-step time, profile a
       K-step scan and divide the total by K.

    ``donate=True`` profiles a TRAIN-STEP-shaped ``fn``: every
    positional arg is donated and ``fn`` must return a tuple whose
    first ``len(args)`` entries are the args' replacements (extra
    returns like the loss are fine).  Without it, state-carrying steps
    hold two copies of params+optimizer state on device — at
    GPT-345M/O5 scale that alone exceeds HBM."""
    from xprof.convert import raw_to_tool_data as _r2t

    if donate:
        jitted = jax.jit(lambda *a: fn(*a, **kwargs),
                         donate_argnums=tuple(range(len(args))))
    else:
        jitted = jax.jit(lambda *a: fn(*a, **kwargs))

    def run(args):
        out = jitted(*args)
        if donate:
            if not isinstance(out, (tuple, list)) or len(out) < len(args):
                raise TypeError(
                    "donate=True requires fn to return a tuple whose "
                    f"first {len(args)} entries replace the donated args; "
                    f"got {type(out).__name__}"
                    + ("" if not isinstance(out, (tuple, list))
                       else f" of length {len(out)}"))
            args = tuple(out[:len(args)])
        return out, args

    out, args = run(args)
    jax.block_until_ready(out)

    def loop():
        out = None
        a = args
        for _ in range(iters):
            out, a = run(a)
        return out

    data = _traced_op_stats(loop, trace_dir)
    return parse_op_stats(data, iters=iters)


def _traced_op_stats(loop: Callable[[], object],
                     trace_dir: Optional[str]):
    """Shared tracing core: run ``loop()`` under ``jax.profiler`` and
    return the raw framework_op_stats tool output."""
    from xprof.convert import raw_to_tool_data as _r2t

    tdir = trace_dir or tempfile.mkdtemp(prefix="apex_tpu_prof_")
    try:
        jax.profiler.start_trace(tdir)
        try:
            jax.block_until_ready(loop())
        finally:
            # always close the process-global profiler session, or every
            # later collect in this process fails with "only one
            # profiler session can be active"
            jax.profiler.stop_trace()
        xplanes = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True)
        if not xplanes:
            raise RuntimeError(f"no xplane.pb written under {tdir}")
        data, _ = _r2t.xspace_to_tool_data(xplanes,
                                           "framework_op_stats", {})
        return data
    finally:
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)


def profile_call(thunk: Callable[[], object], iters: int = 1,
                 trace_dir: Optional[str] = None) -> List[MeasuredOp]:
    """Trace ``iters`` calls of an ALREADY-COMPILED zero-arg callable
    and return per-op device self-times normalized to one call.

    Unlike :func:`collect_device_ops` this wraps nothing in a new
    ``jax.jit`` — use it to profile an existing executable with its
    live (possibly donated) buffers without paying a retrace/recompile.
    The caller is responsible for warmup (typically the timing
    loop that just ran).

    .. note:: With ``iters > 1`` a thunk over a DONATING executable
       must rebind its own operands from each call's outputs (e.g. the
       bench's rn50 ``holder`` pattern) — a closure over fixed donated
       buffers works only at ``iters=1``; the second call would
       dispatch on deleted buffers."""

    def loop():
        out = None
        for _ in range(iters):
            out = thunk()
        return out

    data = _traced_op_stats(loop, trace_dir)
    return parse_op_stats(data, iters=iters)


def parse_op_stats(data, iters: int = 1) -> List[MeasuredOp]:
    """Parse xprof's ``framework_op_stats`` tool output (gviz JSON —
    bytes or str, a table or a list of tables) into device
    :class:`MeasuredOp` rows, normalized to one execution.

    Split out of :func:`collect_device_ops` so the parse is
    regression-testable without TPU hardware: a recorded tool output
    lives at ``tests/data/framework_op_stats_gpt.json`` (the round-4
    GPT-345M train-step capture)."""
    text = data.decode() if isinstance(data, bytes) else data
    tables = json.loads(text)
    table = tables[0] if isinstance(tables, list) else tables
    cols = [c["label"] for c in table["cols"]]
    rows = [dict(zip(cols, [c.get("v") for c in r["c"]]))
            for r in table["rows"]]
    out_rows = []
    for r in rows:
        if r.get("Host/device") != "Device":
            continue
        name = r.get("Operation Name") or ""
        if name == "IDLE":
            continue
        out_rows.append(MeasuredOp(
            name=name,
            op_type=r.get("Operation Type") or "",
            occurrences=float(r.get("#Occurrences") or 0) / iters,
            total_us=float(r.get("Total self-time (us)") or 0.0) / iters,
        ))
    return out_rows


@dataclass
class JoinedRow:
    op: str
    scope: str
    flops: float            # analytical (0 when measured-only)
    bytes: float
    est_us: float           # roofline estimate
    measured_us: float      # device self-time (0 when unmatched)
    matched: bool


def join_measured(records: Sequence[OpRecord],
                  measured: Sequence[MeasuredOp],
                  spec=None) -> List[JoinedRow]:
    """Join analytical rows with measured rows on the canonical
    (op, scope) key, aggregating both sides first (XLA fuses; the jaxpr
    walker unrolls — neither side is 1:1)."""
    spec = spec or device_spec()
    ana: Dict[Tuple[str, str], dict] = collections.defaultdict(
        lambda: {"flops": 0.0, "bytes": 0.0, "est": 0.0})
    for r in records:
        k = canonical_key((r.scope + "/" if r.scope else "") + r.op)
        a = ana[k]
        a["flops"] += r.flops
        a["bytes"] += r.bytes
        a["est"] += r.est_time_us(spec)
    mea: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for m in measured:
        mea[canonical_key(m.name)] += m.total_us

    rows: List[JoinedRow] = []
    consumed: set = set()
    # Pass 2: measured rows whose op the walker RECURSED into
    # (pallas_call bodies, custom calls) carry the call's scope while
    # the analytical rows live under scope/op/...; attribute such a
    # measured row to the aggregate of its (unconsumed) subtree.
    leftovers = {}
    for k, mus in list(mea.items()):
        if k in ana:
            continue
        prefix = (k[1] + "/" if k[1] else "") + k[0]
        subtree = [k2 for k2 in ana
                   if k2 not in consumed
                   and (k2[1] == prefix
                        or k2[1].startswith(prefix + "/"))]
        if not subtree and k[1]:
            # XLA sometimes hoists an op to its enclosing scope (layout
            # transposes/concats); attribute to same-op rows under the
            # measured scope's subtree ('/'-bounded: 'layer/attn' must
            # not swallow 'layer/attn2')
            subtree = [k2 for k2 in ana
                       if k2 not in consumed and k2[0] == k[0]
                       and (k2[1] == k[1]
                            or k2[1].startswith(k[1] + "/"))]
        if subtree:
            agg = {"flops": 0.0, "bytes": 0.0, "est": 0.0}
            for k2 in subtree:
                for f in agg:
                    agg[f] += ana[k2][f]
                consumed.add(k2)
            rows.append(JoinedRow(op=k[0], scope=k[1],
                                  flops=agg["flops"],
                                  bytes=agg["bytes"],
                                  est_us=agg["est"], measured_us=mus,
                                  matched=True))
        else:
            leftovers[k] = mus
        del mea[k]

    for k, a in ana.items():
        mus = mea.pop(k, 0.0)
        if k in consumed:
            if mus > 0.0:
                # the analytical side was attributed to a subtree row;
                # keep this row's MEASURED time (flops zeroed) so the
                # TOTAL still reconciles against device time
                rows.append(JoinedRow(op=k[0], scope=k[1], flops=0.0,
                                      bytes=0.0, est_us=0.0,
                                      measured_us=mus, matched=True))
            continue
        rows.append(JoinedRow(op=k[0], scope=k[1], flops=a["flops"],
                              bytes=a["bytes"], est_us=a["est"],
                              measured_us=mus, matched=mus > 0.0))
    for k, mus in leftovers.items():
        rows.append(JoinedRow(op=k[0], scope=k[1], flops=0.0, bytes=0.0,
                              est_us=0.0, measured_us=mus,
                              matched=False))
    rows.sort(key=lambda r: -(r.measured_us or r.est_us))
    return rows


def measured_report(rows: Sequence[JoinedRow], top: Optional[int] = None
                    ) -> str:
    """TSV: op, scope, flops, bytes, est_us, measured_us, achieved
    TFLOP/s (the reference's output.py table with the measured column
    the nvvp parser supplied)."""
    shown = rows[:top] if top else rows
    lines = ["op\tscope\tflops\tbytes\test_us\tmeasured_us\t"
             "achieved_tflops"]
    for r in shown:
        tf = (r.flops / r.measured_us * 1e-6) if r.measured_us else 0.0
        lines.append(f"{r.op}\t{r.scope}\t{r.flops:.3e}\t{r.bytes:.3e}"
                     f"\t{r.est_us:.1f}\t{r.measured_us:.1f}\t{tf:.1f}")
    tot_meas = sum(r.measured_us for r in rows)
    tot_matched = sum(r.measured_us for r in rows if r.flops > 0)
    lines.append(f"TOTAL\t\t{sum(r.flops for r in rows):.3e}\t"
                 f"{sum(r.bytes for r in rows):.3e}\t"
                 f"{sum(r.est_us for r in rows):.1f}\t{tot_meas:.1f}\t")
    pct = 100.0 * tot_matched / tot_meas if tot_meas else 0.0
    lines.append(f"# measured device time on rows with analytical "
                 f"flops: {tot_matched:.1f} us ({pct:.1f}% of device "
                 f"total)")
    return "\n".join(lines)


def profile_measured(fn: Callable, *args, iters: int = 3,
                     **kwargs) -> List[JoinedRow]:
    """One-call pipeline: analytical walk + profiled run + join.

    Returns rows where hot ops carry BOTH analytical flops/bytes and
    measured device microseconds; print with :func:`measured_report`.
    """
    records = analyze(fn, *args, **kwargs)
    measured = collect_device_ops(fn, *args, iters=iters, **kwargs)
    return join_measured(records, measured)
