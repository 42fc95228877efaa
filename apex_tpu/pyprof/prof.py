"""Analytical per-op FLOP/byte attribution over jaxprs.

Parity surface for ``apex/pyprof/prof/`` (~30 files of per-op analytical
models: conv at prof/conv.py:236, blas at prof/blas.py:340, pointwise,
reductions, index/slice/join/mutate at :419) and the ``pyprof.parse``
pipeline.  The reference reconstructs op identity from NVTX markers in an
nvprof SQLite dump; on TPU the program IS available as a jaxpr, so the
analyzer walks it directly — no marker round-trip — and attributes each
equation to its ``named_scope`` stack (the annotations from
:mod:`apex_tpu.pyprof.nvtx`).

Output: a list of :class:`OpRecord` and a TSV report (the reference's
``prof/output.py`` table), with FLOPs, bytes moved, arithmetic intensity,
and a roofline time estimate against the device's peak specs.  Estimated
time is analytical (the reference's is too — measured kernel time comes
from nvprof; here the measured cross-check is ``measure()``'s wall-clock
on the whole function, plus XLA's own ``cost_analysis``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

# ---------------------------------------------------------------------------
# Device roofline specs (public figures; used only for the time-estimate
# column, clearly labeled as analytical).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    name: str
    peak_bf16_tflops: float
    peak_hbm_gbps: float


_DEVICE_SPECS = {
    # Google-published peak numbers.
    "v5 lite": DeviceSpec("TPU v5e", 197.0, 819.0),
    "v5e": DeviceSpec("TPU v5e", 197.0, 819.0),
    "v5p": DeviceSpec("TPU v5p", 459.0, 2765.0),
    "v4": DeviceSpec("TPU v4", 275.0, 1228.0),
    "v6": DeviceSpec("TPU v6e", 918.0, 1640.0),
    "cpu": DeviceSpec("host CPU", 1.0, 50.0),
}


def device_spec(device=None) -> DeviceSpec:
    """The peak-rate row of ``device`` (default: ``jax.devices()[0]``).
    The ``cpu`` row is a placeholder for host runs only; an accelerator
    whose kind is not in the table raises ``KeyError`` — a rate computed
    against another device's peak is wrong, not approximate."""
    d = device or jax.devices()[0]
    if d.platform == "cpu":
        return _DEVICE_SPECS["cpu"]
    kind = d.device_kind.lower()
    for key, spec in _DEVICE_SPECS.items():
        if key != "cpu" and key in kind:
            return spec
    raise KeyError(
        f"no peak-rate row for {d.platform} device kind "
        f"{d.device_kind!r}: add it to pyprof.prof._DEVICE_SPECS "
        f"with its source")


# ---------------------------------------------------------------------------
# Records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpRecord:
    """One jaxpr equation's cost attribution (the reference's per-kernel
    TSV row, ref: apex/pyprof/prof/output.py fields: idx, dir, op, params,
    flops, bytes, silicon time)."""

    index: int
    op: str                   # primitive name
    scope: str                # named_scope stack ("" at top level)
    params: str               # shape summary, e.g. "(128,512)x(512,512)"
    flops: float              # multiply-add counted as 2, reference style
    bytes: float              # operand + result bytes
    count: int = 1            # trip multiplier (scan length etc.)

    @property
    def intensity(self) -> float:
        return self.flops / self.bytes if self.bytes else 0.0

    def est_time_us(self, spec: DeviceSpec) -> float:
        if not (self.flops or self.bytes):
            return 0.0
        t_flops = self.flops / (spec.peak_bf16_tflops * 1e12)
        t_bytes = self.bytes / (spec.peak_hbm_gbps * 1e9)
        return max(t_flops, t_bytes) * 1e6


def _nbytes(aval) -> float:
    try:
        return float(np.prod(aval.shape, dtype=np.float64)
                     * jnp.dtype(aval.dtype).itemsize)
    except (TypeError, ValueError, AttributeError, OverflowError):
        return 0.0


def _numel(aval) -> float:
    try:
        return float(np.prod(aval.shape, dtype=np.float64))
    except (TypeError, ValueError, AttributeError, OverflowError):
        return 0.0


def _shape_str(avals) -> str:
    def one(a):
        try:
            return "(" + ",".join(str(int(d)) for d in a.shape) + ")"
        except (TypeError, ValueError, AttributeError):
            return "?"
    return "x".join(one(a) for a in avals)


# ---------------------------------------------------------------------------
# Per-primitive FLOP models (ref: apex/pyprof/prof/{blas,conv,pointwise,
# reductions,...}.py analytical formulas)
# ---------------------------------------------------------------------------

def _dot_general_flops(eqn) -> float:
    """2*M*N*K*batch (ref: prof/blas.py:340 GEMM model)."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = np.prod([lhs.shape[i] for i in lb], dtype=np.float64) if lb \
        else 1.0
    k = np.prod([lhs.shape[i] for i in lc], dtype=np.float64) if lc else 1.0
    m = np.prod([lhs.shape[i] for i in range(len(lhs.shape))
                 if i not in tuple(lc) + tuple(lb)], dtype=np.float64)
    n = np.prod([rhs.shape[i] for i in range(len(rhs.shape))
                 if i not in tuple(rc) + tuple(rb)], dtype=np.float64)
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    """2 * out_numel * (Cin/groups) * prod(kernel_spatial)
    (ref: prof/conv.py:236 conv model).  XLA's kernel in-feature dim
    (rhs_spec[1]) is already Cin/feature_group_count, so grouping needs
    no extra division here."""
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    k_spatial = np.prod([rhs.shape[i] for i in dn.rhs_spec[2:]],
                        dtype=np.float64)
    cin_per_group = rhs.shape[dn.rhs_spec[1]]
    return 2.0 * _numel(out) * cin_per_group * k_spatial


_TRANSCENDENTAL = {
    "exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan", "erf",
    "erfc", "erf_inv", "logistic", "rsqrt", "sqrt", "pow", "cbrt",
    "atan2", "digamma", "lgamma",
}
_POINTWISE_2 = {"div", "rem"}
_CHEAP_POINTWISE = {
    "add", "sub", "mul", "max", "min", "neg", "abs", "sign", "floor",
    "ceil", "round", "and", "or", "not", "xor", "eq", "ne", "lt", "le",
    "gt", "ge", "select_n", "clamp", "nextafter", "integer_pow",
    "add_any", "square",
}
_REDUCTIONS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "reduce_precision", "cumsum",
    "cummax", "cummin", "cumprod", "cumlogsumexp",
}
_DATA_MOVEMENT = {
    "reshape", "transpose", "broadcast_in_dim", "concatenate", "slice",
    "dynamic_slice", "dynamic_update_slice", "gather", "scatter",
    "scatter-add", "scatter_add", "rev", "pad", "squeeze", "convert_element_type",
    "bitcast_convert_type", "copy", "iota", "split",
}
_COLLECTIVES = {
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "pbroadcast",
}


def _eqn_cost(eqn) -> Tuple[float, float]:
    """(flops, bytes) for one equation."""
    name = eqn.primitive.name
    in_bytes = sum(_nbytes(v.aval) for v in eqn.invars
                   if hasattr(v, "aval"))
    out_bytes = sum(_nbytes(v.aval) for v in eqn.outvars)
    bytes_ = in_bytes + out_bytes
    out_numel = sum(_numel(v.aval) for v in eqn.outvars)

    if name == "dot_general":
        return _dot_general_flops(eqn), bytes_
    if name == "conv_general_dilated":
        return _conv_flops(eqn), bytes_
    if name in _TRANSCENDENTAL:
        # transcendental ~ 10 flops/elem (reference's pointwise op table
        # distinguishes transcendental cost, ref: prof/pointwise.py)
        return 10.0 * out_numel, bytes_
    if name in _POINTWISE_2:
        return 2.0 * out_numel, bytes_
    if name in _CHEAP_POINTWISE:
        return 1.0 * out_numel, bytes_
    if name in _REDUCTIONS:
        in_numel = sum(_numel(v.aval) for v in eqn.invars
                       if hasattr(v, "aval"))
        return in_numel, bytes_
    if name in _DATA_MOVEMENT or name in _COLLECTIVES:
        return 0.0, bytes_
    return 0.0, bytes_


# Sub-jaxpr trip-count handling -------------------------------------------

# Branch-cost memo for cond selection.  Keyed by jaxpr object id; the
# jaxpr itself is stored as the value's first element so the id cannot be
# recycled while the memo is alive.  Without this, nested conds make the
# analyzer re-walk branches exponentially.
_BRANCH_FLOPS_MEMO: dict = {}


def _branch_flops(closed) -> float:
    key = id(closed)
    hit = _BRANCH_FLOPS_MEMO.get(key)
    if hit is not None and hit[0] is closed:
        return hit[1]
    recs = _walk(closed, scope="", mult=1, out=None)
    cost = sum(r.flops for r in recs)
    _BRANCH_FLOPS_MEMO[key] = (closed, cost)
    if len(_BRANCH_FLOPS_MEMO) > 4096:
        _BRANCH_FLOPS_MEMO.clear()
    return cost


def _subjaxprs(eqn):
    """Yield (closed_jaxpr, trip_count) pairs for call-like primitives."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "scan":
        yield p["jaxpr"], int(p["length"])
        return
    if name == "while":
        # unknown trip count: count one iteration, scope-tagged
        yield p["body_jaxpr"], 1
        return
    if name == "cond":
        # worst-case branch (reference reports kernels actually run; a
        # static analyzer takes the max)
        branches = p["branches"]
        costs = [_branch_flops(br) for br in branches]
        best = int(np.argmax(costs)) if branches else 0
        yield branches[best], 1
        return
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p:
            sub = p[key]
            yield sub, 1
            return


def _walk(jaxpr, scope: str, mult: int,
          out: Optional[List[OpRecord]],
          counter: Optional[List[int]] = None) -> List[OpRecord]:
    if out is None:
        out = []
    if counter is None:
        counter = [0]
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        subs = list(_subjaxprs(eqn))
        eqn_scope = scope
        try:
            ns = str(eqn.source_info.name_stack)
            if ns:
                eqn_scope = (scope + "/" + ns) if scope else ns
        except AttributeError:
            pass  # jaxpr without source info (synthetic/cached)
        if subs:
            inner = f"{eqn.primitive.name}"
            for sub, trips in subs:
                _walk(sub,
                      scope=(eqn_scope + "/" + inner) if eqn_scope
                      else inner,
                      mult=mult * trips, out=out,
                      counter=counter)
            continue
        flops, bytes_ = _eqn_cost(eqn)
        rec = OpRecord(
            index=counter[0],
            op=eqn.primitive.name,
            scope=eqn_scope,
            params=_shape_str([v.aval for v in eqn.invars
                               if hasattr(v, "aval")]),
            flops=flops * mult,
            bytes=bytes_ * mult,
            count=mult,
        )
        counter[0] += 1
        out.append(rec)
    return out


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def analyze(fn: Callable, *args, **kwargs) -> List[OpRecord]:
    """Trace ``fn`` and return per-op cost records
    (the reference pipeline's ``parse`` + ``prof`` stages in one step)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _walk(closed, scope="", mult=1, out=None)


def total_flops(records: Sequence[OpRecord]) -> float:
    return sum(r.flops for r in records)


def total_bytes(records: Sequence[OpRecord]) -> float:
    return sum(r.bytes for r in records)


def summary_by_op(records: Sequence[OpRecord]) -> Dict[str, dict]:
    """Aggregate flops/bytes per primitive (the reference's per-op-class
    rollup)."""
    agg: Dict[str, dict] = {}
    for r in records:
        a = agg.setdefault(r.op, {"calls": 0, "flops": 0.0, "bytes": 0.0})
        a["calls"] += r.count
        a["flops"] += r.flops
        a["bytes"] += r.bytes
    return dict(sorted(agg.items(), key=lambda kv: -kv[1]["flops"]))


def report(records: Sequence[OpRecord], spec: Optional[DeviceSpec] = None,
           top: Optional[int] = None) -> str:
    """TSV report, one row per op (ref: apex/pyprof/prof/output.py).

    Columns: idx, op, scope, params, count, flops, bytes, intensity
    (flops/byte), est_us (roofline vs ``spec``).
    """
    spec = spec or device_spec()
    rows = sorted(records, key=lambda r: -r.flops)
    if top:
        rows = rows[:top]
    lines = ["idx\top\tscope\tparams\tcount\tflops\tbytes\t"
             "intensity\test_us"]
    for r in rows:
        lines.append(
            f"{r.index}\t{r.op}\t{r.scope}\t{r.params}\t{r.count}\t"
            f"{r.flops:.3e}\t{r.bytes:.3e}\t{r.intensity:.2f}\t"
            f"{r.est_time_us(spec):.2f}")
    ftot, btot = total_flops(records), total_bytes(records)
    est = sum(r.est_time_us(spec) for r in records)
    lines.append(f"TOTAL\t\t\t\t\t{ftot:.3e}\t{btot:.3e}\t"
                 f"{(ftot / btot if btot else 0):.2f}\t{est:.2f}")
    return "\n".join(lines)


def xla_cost_analysis(fn: Callable, *args, **kwargs) -> dict:
    """XLA's own cost model for cross-checking the analytical walker
    (flops here are post-fusion/optimization)."""
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def measure(fn: Callable, *args, iters: int = 10, **kwargs) -> float:
    """Measured wall-clock seconds per call (device-synced), the
    empirical cross-check column."""
    import time

    jitted = jax.jit(fn)
    out = jitted(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jitted(*args, **kwargs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters
