"""Kernel-parity audit: every Pallas kernel must have a jnp twin and a
test exercising both.

The repo's kernel discipline (docs/PARITY.md lineage) is that each
``pl.pallas_call`` site in ``apex_tpu/ops`` is an *implementation* of
math that also exists as a plain-jnp twin — the twin is the XLA
fallback inside ``shard_map`` manual axes, the CPU/interpret oracle in
tests, and the spec a reviewer diffs the kernel against.  A kernel
whose twin (or twin test) quietly disappears keeps passing CI right up
until a Mosaic regression ships.  This audit makes the pairing a
structural invariant:

* every function in ``apex_tpu/ops`` containing a ``pallas_call`` must
  appear in :data:`KERNEL_TWINS` (APX401);
* the registered twin must exist where the registry says (APX401);
* at least one registered test file must reference BOTH the public
  entry point and the twin by name (APX402).

Run via ``python -m apex_tpu.analysis --check`` (self-hosted in CI).
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

from .linter import Finding

__all__ = ["KERNEL_TWINS", "TwinSpec", "audit_kernel_parity",
           "pallas_call_sites"]


@dataclasses.dataclass(frozen=True)
class TwinSpec:
    """Registry row for one kernel-bearing function."""

    public: str              # public symbol tests dispatch the kernel via
    twin: str                # jnp twin symbol
    twin_module: str         # repo-relative file defining the twin
    tests: Tuple[str, ...]   # test files that must reference public+twin


def _spec(public: str, twin: str, twin_module: str,
          *tests: str) -> TwinSpec:
    return TwinSpec(public=public, twin=twin, twin_module=twin_module,
                    tests=tuple(tests))


# (ops module basename, enclosing top-level function) -> TwinSpec
KERNEL_TWINS: Dict[Tuple[str, str], TwinSpec] = {
    # flash attention: every fwd/bwd/packed/E-layout kernel family is
    # specified by the dense mha_reference
    **{("flash_attention.py", fn): _spec(
        "flash_attention", "mha_reference",
        "apex_tpu/ops/flash_attention.py",
        "tests/test_flash_attention.py")
       for fn in ("_flash_fwd", "_flash_fwd_packed", "_flash_bwd",
                  "_flash_bwd_packed", "_fwd_e_driver",
                  "_flash_fwd_e_blocked", "_bwd_e_driver",
                  "_flash_bwd_e_blocked")},
    # flash decode: the paged single-query serving kernel is specified
    # by the dense gather-and-softmax reference (also the naive decode
    # baseline, ``decode_attention="reference"``)
    ("flash_decode.py", "_decode_paged"): _spec(
        "flash_decode", "paged_attention_reference",
        "apex_tpu/ops/flash_decode.py", "tests/test_serving.py"),
    # multi-token paged attention (ISSUE-12): the speculative-verify /
    # chunked-prefill chunk kernel, specified by the dense per-row
    # causal gather reference
    ("flash_decode.py", "_decode_paged_multi"): _spec(
        "flash_decode_multi", "paged_attention_multi_reference",
        "apex_tpu/ops/flash_decode.py", "tests/test_serving.py"),
    # latent decode (ISSUE-34): paged attention over a latent cache,
    # one driver for the single-token and the t-token form, specified
    # by the dense gather twins
    ("latent_decode.py", "_latent_paged"): _spec(
        "latent_decode", "latent_attention_reference",
        "apex_tpu/ops/latent_decode.py", "tests/test_serving_mla_moe.py"),
    ("layer_norm.py", "_ln_forward"): _spec(
        "layer_norm", "_layer_norm_reference",
        "apex_tpu/ops/layer_norm.py", "tests/test_layer_norm.py"),
    ("layer_norm.py", "_ln_backward"): _spec(
        "layer_norm", "_layer_norm_reference",
        "apex_tpu/ops/layer_norm.py", "tests/test_layer_norm.py"),
    ("scaled_softmax.py", "_causal_fwd"): _spec(
        "scaled_upper_triang_masked_softmax", "_causal_softmax_xla",
        "apex_tpu/ops/scaled_softmax.py", "tests/test_fused_layers.py"),
    ("scaled_softmax.py", "_softmax_backward"): _spec(
        "scaled_upper_triang_masked_softmax", "_causal_softmax_xla",
        "apex_tpu/ops/scaled_softmax.py", "tests/test_fused_layers.py"),
    ("scaled_softmax.py", "_masked_fwd"): _spec(
        "scaled_masked_softmax", "_masked_softmax_xla",
        "apex_tpu/ops/scaled_softmax.py", "tests/test_fused_layers.py"),
    # the shared elementwise dispatcher carries every fused-optimizer
    # kernel; _adam_jnp is the per-leaf twin the optimizers fall back to
    ("fused_optim.py", "_elementwise_call"): _spec(
        "adam_update", "_adam_jnp",
        "apex_tpu/optimizers/fused_adam.py", "tests/test_optimizers.py",
        "tests/test_fused_pipeline.py"),
    ("fused_pipeline.py", "_norm_finite_pallas"): _spec(
        "grad_norm_finite", "_norm_finite_jnp",
        "apex_tpu/ops/fused_pipeline.py", "tests/test_fused_pipeline.py"),
    # int8 weight-only matmul (ISSUE-16 Q8 tier): GEMV decode path and
    # tiled prefill path, both specified by the scale-after-matmul
    # fp32 reference (also the XLA fallback off TPU)
    **{("quant_matmul.py", fn): _spec(
        "quant_matmul", "quant_matmul_reference",
        "apex_tpu/ops/quant_matmul.py", "tests/test_quant_matmul.py")
       for fn in ("_quant_gemv", "_quant_tiled")},
    # fused MoE routing + dispatch (ISSUE-19): softmax/top-k/capacity
    # slotting/scatter in one pass, specified by the GShard cumsum
    # reference (bit-identical keep/slot decisions across backends)
    ("moe_routing.py", "_route_dispatch_pallas"): _spec(
        "moe_route_dispatch", "moe_route_dispatch_reference",
        "apex_tpu/ops/moe_routing.py", "tests/test_moe_routing.py"),
}


def pallas_call_sites(ops_dir: Path) -> List[Tuple[str, str, int]]:
    """(module basename, enclosing top-level function, line) for every
    ``pallas_call`` under ``ops_dir``."""
    def is_pallas_call(sub: ast.AST) -> bool:
        if not isinstance(sub, ast.Call):
            return False
        f = sub.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            getattr(f, "id", None)
        return name == "pallas_call"

    sites: List[Tuple[str, str, int]] = []
    for py in sorted(ops_dir.glob("*.py")):
        tree = ast.parse(py.read_text())
        claimed: set = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(node):
                    if is_pallas_call(sub) and id(sub) not in claimed:
                        claimed.add(id(sub))
                        sites.append((py.name, node.name, sub.lineno))
        for sub in ast.walk(tree):  # module scope / lambda leftovers
            if is_pallas_call(sub) and id(sub) not in claimed:
                sites.append((py.name, "<module>", sub.lineno))
    return sites


def _defines(path: Path, symbol: str) -> bool:
    if not path.exists():
        return False
    tree = ast.parse(path.read_text())
    return any(isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef))
               and n.name == symbol for n in tree.body)


def audit_kernel_parity(*, repo_root: str = ".") -> List[Finding]:
    repo = Path(repo_root).resolve()
    ops_dir = repo / "apex_tpu" / "ops"
    findings: List[Finding] = []
    checked_specs = set()
    for module, fn, line in pallas_call_sites(ops_dir):
        rel = f"apex_tpu/ops/{module}"
        spec = KERNEL_TWINS.get((module, fn))
        if spec is None:
            findings.append(Finding(
                path=rel, line=line, col=0, rule="APX401",
                severity="error",
                message=f"pallas_call in '{fn}' has no registered jnp "
                        f"twin — add a KERNEL_TWINS entry in "
                        f"apex_tpu/analysis/parity.py",
                symbol=f"{fn}.unregistered"))
            continue
        if (module, fn) in checked_specs:
            continue
        checked_specs.add((module, fn))
        if not _defines(repo / spec.twin_module, spec.twin):
            findings.append(Finding(
                path=rel, line=line, col=0, rule="APX401",
                severity="error",
                message=f"registered twin '{spec.twin}' for kernel "
                        f"'{fn}' is not defined in {spec.twin_module}",
                symbol=f"{fn}.missing_twin"))
            continue
        referenced = False
        for test in spec.tests:
            tp = repo / test
            if not tp.exists():
                continue
            text = tp.read_text()
            if spec.public in text and spec.twin in text:
                referenced = True
                break
        if not referenced:
            findings.append(Finding(
                path=rel, line=line, col=0, rule="APX402",
                severity="error",
                message=f"no test in {list(spec.tests)} references "
                        f"both '{spec.public}' and twin '{spec.twin}' "
                        f"— kernel/twin parity is untested",
                symbol=f"{fn}.untested"))
    return findings
