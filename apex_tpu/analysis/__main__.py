"""CLI for apex_tpu.analysis — the repo's self-hosted static pass.

    python -m apex_tpu.analysis --check          # lint + parity vs baseline
    python -m apex_tpu.analysis --check --paths a.py b.py   # changed-file
    python -m apex_tpu.analysis --check-hlo      # compiled-graph audit
    python -m apex_tpu.analysis --check-sharding # SPMD plan audit
    python -m apex_tpu.analysis --check-concurrency  # APX8xx lock/signal audit
    python -m apex_tpu.analysis --check-protocol # APX9xx wire-protocol audit
    python -m apex_tpu.analysis --update-baseline
    python -m apex_tpu.analysis --update-hlo-baseline
    python -m apex_tpu.analysis --update-sharding-baseline
    python -m apex_tpu.analysis --flag-table     # print the env-flag table
    python -m apex_tpu.analysis --rule-table     # print the APX rule table
    python -m apex_tpu.analysis --check-docs     # docs table drift guard
    python -m apex_tpu.analysis --write-docs     # regenerate the docs tables
    python -m apex_tpu.analysis --smoke          # sanitizer smoke (GPT step)

Exit status: 0 = clean, 1 = findings / drift / recompiles.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

from .flags import render_flag_table
from .linter import DEFAULT_BASELINE, run_check, write_baseline, lint_paths
from .rules import render_rule_table

# Every generated docs table: (file, begin marker, end marker, render).
# --write-docs regenerates all of them in place; --check-docs fails on
# any drift.
_GEN = "(generated: python -m apex_tpu.analysis --write-docs)"
DOCS_TABLES = (
    ("docs/api/ops.md",
     f"<!-- apex-flag-table:begin {_GEN} -->",
     "<!-- apex-flag-table:end -->",
     render_flag_table),
    ("docs/api/analysis.md",
     f"<!-- apex-rule-table:begin {_GEN} -->",
     "<!-- apex-rule-table:end -->",
     render_rule_table),
)


def _docs_block(repo_root: str, doc: str, begin: str,
                end: str) -> tuple[Path, str, int, int]:
    p = Path(repo_root) / doc
    text = p.read_text()
    try:
        a = text.index(begin) + len(begin)
        b = text.index(end)
    except ValueError:
        raise SystemExit(
            f"{doc} is missing the table markers "
            f"({begin!r} ... {end!r})")
    return p, text, a, b


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.analysis",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--check", action="store_true",
                    help="lint apex_tpu + kernel-parity audit against "
                         "the baseline (default action)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline to accept all current "
                         "findings")
    ap.add_argument("--check-hlo", action="store_true",
                    help="compiled-graph audit: lower every registered "
                         "entry point and check donation, dtype "
                         "promotion, the collective census, host "
                         "transfers, and peak live memory against "
                         "tools/hlo_baseline.json")
    ap.add_argument("--update-hlo-baseline", action="store_true",
                    help="rewrite tools/hlo_baseline.json from the "
                         "current lowerings (censuses + memory only; "
                         "APX601/602/604 findings must still be fixed "
                         "or suppressed)")
    ap.add_argument("--check-sharding", action="store_true",
                    help="SPMD sharding audit: compile every "
                         "plan-carrying entry point under its mesh "
                         "and check declared-vs-propagated shardings, "
                         "reshard chains, collective budgets, overlap "
                         "preconditions, and per-device memory "
                         "against tools/sharding_baseline.json "
                         "(APX701-705; needs the 8-device "
                         "host-platform mesh)")
    ap.add_argument("--check-concurrency", action="store_true",
                    help="host-concurrency audit (APX801-805): lock "
                         "discipline via guard inference, "
                         "lock-acquisition-order cycles aggregated "
                         "across modules, flag-only signal handlers, "
                         "blocking calls under locks, and thread-"
                         "target jit dispatch outside a device pin, "
                         "against tools/concurrency_baseline.txt "
                         "(committed empty; stale entries fail)")
    ap.add_argument("--update-concurrency-baseline",
                    action="store_true",
                    help="rewrite tools/concurrency_baseline.txt to "
                         "accept all current APX8xx findings (the "
                         "repo commits it EMPTY: fix, don't "
                         "baseline)")
    ap.add_argument("--check-protocol", action="store_true",
                    help="wire-protocol + resource-lifecycle audit "
                         "(APX901-905): serving/ + resilience/ "
                         "checked against the ProtocolSpec registry "
                         "in serving/control_plane.py — deadline "
                         "discipline, op/header-field drift matched "
                         "across parent and child, socket/subprocess/"
                         "tempdir lifecycle, retry-safety — against "
                         "tools/protocol_baseline.txt (committed "
                         "empty; stale entries fail)")
    ap.add_argument("--update-protocol-baseline", action="store_true",
                    help="rewrite tools/protocol_baseline.txt to "
                         "accept all current APX9xx findings (the "
                         "repo commits it EMPTY: fix, don't "
                         "baseline)")
    ap.add_argument("--update-sharding-baseline", action="store_true",
                    help="rewrite tools/sharding_baseline.json "
                         "(plans + per-device memory + censuses) from "
                         "the current compilations; APX701-703 "
                         "findings must still be fixed or suppressed")
    ap.add_argument("--entry", action="append", default=None,
                    help="restrict --check-hlo/--check-sharding/"
                         "--update-*-baseline to this entry point "
                         "(repeatable)")
    ap.add_argument("--paths", nargs="+", default=None, metavar="FILE",
                    help="with --check: lint ONLY these repo-relative "
                         "files (the changed-file pre-commit fast "
                         "path; skips the kernel-parity audit and "
                         "baseline-staleness judgment — full CI keeps "
                         "the full walk)")
    ap.add_argument("--flag-table", action="store_true",
                    help="print the generated env-flag markdown table")
    ap.add_argument("--rule-table", action="store_true",
                    help="print the generated APX rule markdown table")
    ap.add_argument("--check-docs", action="store_true",
                    help="fail if any generated docs table drifted "
                         "from its registry")
    ap.add_argument("--write-docs", action="store_true",
                    help="regenerate the docs tables in place")
    ap.add_argument("--smoke", action="store_true",
                    help="run the sanitizer smoke: the standalone-GPT "
                         "step must compile exactly once after warmup")
    ap.add_argument("--scan-steps", type=int, default=0, metavar="K",
                    help="with --smoke: drive the batched-step scan "
                         "driver (K steps per jit call) instead of "
                         "the per-step loop — one compile for the "
                         "whole N-step run")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline path (default {DEFAULT_BASELINE})")
    ap.add_argument("--root", default=".",
                    help="repo root to lint from (default .)")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as JSON lines")
    args = ap.parse_args(argv)

    if args.flag_table:
        print(render_flag_table())
        return 0

    if args.rule_table:
        print(render_rule_table())
        return 0

    if args.check_docs or args.write_docs:
        rc = 0
        for doc, begin, end, render in DOCS_TABLES:
            p, text, a, b = _docs_block(args.root, doc, begin, end)
            want = "\n" + render() + "\n"
            have = text[a:b]
            if args.write_docs:
                if have != want:
                    p.write_text(text[:a] + want + text[b:])
                    print(f"[analysis] {doc} table updated")
                else:
                    print(f"[analysis] {doc} table already current")
            elif have != want:
                print(f"[analysis] FAIL: {doc} table drifted from the "
                      f"registry — run 'python -m apex_tpu.analysis "
                      f"--write-docs'", file=sys.stderr)
                rc = 1
            else:
                print(f"[analysis] {doc} table matches the registry")
        return rc

    if args.check_hlo or args.update_hlo_baseline:
        from ..testing.entry_points import ENTRY_POINTS
        from .hlo import (audit_entry_points, run_hlo_check,
                          write_hlo_baseline)

        if args.entry:
            # a typo'd name must not produce a do-nothing audit that
            # exits 0 claiming "hlo clean"
            unknown = sorted(set(args.entry) - set(ENTRY_POINTS))
            if unknown:
                ap.error(f"unknown entry point(s) {unknown}; "
                         f"registered: {sorted(ENTRY_POINTS)}")
        if args.update_hlo_baseline:
            audits = audit_entry_points(args.root, names=args.entry)
            leftover = [f for a in audits.values() for f in a.findings]
            write_hlo_baseline(audits, repo_root=args.root)
            print(f"[analysis] hlo baseline rewritten: "
                  f"{len(audits)} entry point(s)")
            for f in leftover:
                print(f"[analysis] note: unbaselined finding remains "
                      f"(fix or suppress): {f.render()}",
                      file=sys.stderr)
            return 0
        unsuppressed, stale, audits = run_hlo_check(args.root,
                                                    names=args.entry)
        for f in sorted(unsuppressed, key=lambda x: (x.path, x.line)):
            if args.json:
                print(json.dumps(dataclasses.asdict(f)))
            else:
                print(f.render())
        for k in sorted(stale):
            print(f"[analysis] stale hlo suppression (finding no "
                  f"longer fires — delete the line): {k}",
                  file=sys.stderr)
        if unsuppressed or stale:
            print(f"[analysis] FAIL: {len(unsuppressed)} unsuppressed "
                  f"hlo finding(s), {len(stale)} stale suppression(s)",
                  file=sys.stderr)
            return 1
        ncoll = sum(len(a.collectives) for a in audits.values())
        print(f"[analysis] hlo clean: {len(audits)} entry point(s) "
              f"audited, {ncoll} collective op(s) match the census, "
              f"0 unsuppressed findings")
        return 0

    if args.check_sharding or args.update_sharding_baseline:
        from ..testing.entry_points import ENTRY_POINTS
        from .sharding import (audit_sharding, run_sharding_check,
                               write_sharding_baseline)

        if args.entry:
            unknown = sorted(set(args.entry) - set(ENTRY_POINTS))
            if unknown:
                ap.error(f"unknown entry point(s) {unknown}; "
                         f"registered: {sorted(ENTRY_POINTS)}")
        if args.update_sharding_baseline:
            audits = audit_sharding(args.root, names=args.entry)
            write_sharding_baseline(audits, repo_root=args.root)
            print(f"[analysis] sharding baseline rewritten: "
                  f"{len(audits)} planned entry point(s)")
            leftover = [f for a in audits.values() for f in a.findings]
            for f in leftover:
                print(f"[analysis] note: unbaselined finding remains "
                      f"(fix or suppress): {f.render()}",
                      file=sys.stderr)
            return 0
        unsuppressed, advisories, stale, audits = run_sharding_check(
            args.root, names=args.entry)
        for f in sorted(advisories, key=lambda x: (x.path, x.line)):
            # APX704 is advisory by design: printed, never red
            print(f.render() if not args.json
                  else json.dumps(dataclasses.asdict(f)))
        for f in sorted(unsuppressed, key=lambda x: (x.path, x.line)):
            if args.json:
                print(json.dumps(dataclasses.asdict(f)))
            else:
                print(f.render())
        for k in sorted(stale):
            print(f"[analysis] stale sharding suppression (finding no "
                  f"longer fires — delete the line): {k}",
                  file=sys.stderr)
        if unsuppressed or stale:
            print(f"[analysis] FAIL: {len(unsuppressed)} unsuppressed "
                  f"sharding finding(s), {len(stale)} stale "
                  f"suppression(s)", file=sys.stderr)
            return 1
        ncoll = sum(sum(a.census.values()) for a in audits.values())
        print(f"[analysis] sharding clean: {len(audits)} planned "
              f"entry point(s) audited under their meshes, {ncoll} "
              f"collective op(s) within budget, "
              f"{len(advisories)} advisory(ies), 0 unsuppressed "
              f"findings")
        return 0

    if args.check_concurrency or args.update_concurrency_baseline:
        from .concurrency import (DEFAULT_BASELINE as CONC_BASELINE,
                                  lint_concurrency_paths,
                                  run_concurrency_check,
                                  write_concurrency_baseline)

        if args.update_concurrency_baseline:
            findings, _ = lint_concurrency_paths(repo_root=args.root)
            write_concurrency_baseline(findings, repo_root=args.root)
            print(f"[analysis] concurrency baseline rewritten with "
                  f"{len(set(f.key for f in findings))} entries")
            return 0
        unsuppressed, stale, regions = run_concurrency_check(
            repo_root=args.root)
        for f in sorted(unsuppressed, key=lambda x: (x.path, x.line)):
            if args.json:
                print(json.dumps(dataclasses.asdict(f)))
            else:
                print(f.render())
        for k in sorted(stale):
            print(f"[analysis] stale concurrency baseline entry "
                  f"(finding no longer fires — delete the line): {k}",
                  file=sys.stderr)
        if unsuppressed or stale:
            print(f"[analysis] FAIL: {len(unsuppressed)} unsuppressed "
                  f"concurrency finding(s), {len(stale)} stale "
                  f"baseline entr(ies)", file=sys.stderr)
            return 1
        print(f"[analysis] concurrency clean: {regions} lock "
              f"region(s) audited, 0 unsuppressed APX8xx findings "
              f"(baseline {CONC_BASELINE} empty-current)")
        return 0

    if args.check_protocol or args.update_protocol_baseline:
        from .protocol import (DEFAULT_BASELINE as PROTO_BASELINE,
                               lint_protocol_paths,
                               run_protocol_check,
                               write_protocol_baseline)

        if args.update_protocol_baseline:
            findings, _ = lint_protocol_paths(repo_root=args.root)
            write_protocol_baseline(findings, repo_root=args.root)
            print(f"[analysis] protocol baseline rewritten with "
                  f"{len(set(f.key for f in findings))} entries")
            return 0
        unsuppressed, stale, n_ops = run_protocol_check(
            repo_root=args.root)
        for f in sorted(unsuppressed, key=lambda x: (x.path, x.line)):
            if args.json:
                print(json.dumps(dataclasses.asdict(f)))
            else:
                print(f.render())
        for k in sorted(stale):
            print(f"[analysis] stale protocol baseline entry "
                  f"(finding no longer fires — delete the line): {k}",
                  file=sys.stderr)
        if unsuppressed or stale:
            print(f"[analysis] FAIL: {len(unsuppressed)} unsuppressed "
                  f"protocol finding(s), {len(stale)} stale "
                  f"baseline entr(ies)", file=sys.stderr)
            return 1
        print(f"[analysis] protocol clean: {n_ops} declared op(s) "
              f"audited across serving/ + resilience/, 0 "
              f"unsuppressed APX9xx findings (baseline "
              f"{PROTO_BASELINE} empty-current)")
        return 0

    if args.smoke:
        from .sanitizer import sanitize_smoke

        n = sanitize_smoke(scan_steps=args.scan_steps)
        return 0 if n == 0 else 1

    if args.update_baseline:
        findings = lint_paths(repo_root=args.root)
        from .parity import audit_kernel_parity

        findings.extend(audit_kernel_parity(repo_root=args.root))
        write_baseline(findings, args.baseline, repo_root=args.root)
        print(f"[analysis] baseline rewritten with "
              f"{len(set(f.key for f in findings))} entries")
        return 0

    # default: --check
    unsuppressed, stale = run_check(baseline=args.baseline,
                                    repo_root=args.root,
                                    paths=args.paths)
    if args.paths:
        # the changed-file fast path also covers the APX9xx protocol
        # rules for any named file inside the protocol trees (full
        # CI keeps the dedicated --check-protocol walk with its own
        # staleness judgment)
        from .protocol import (DEFAULT_BASELINE as PROTO_BASELINE,
                               lint_protocol_paths)

        proto, _ = lint_protocol_paths(repo_root=args.root,
                                       paths=args.paths)
        from .linter import load_baseline as _load_baseline

        proto_base = _load_baseline(PROTO_BASELINE,
                                    repo_root=args.root)
        unsuppressed = list(unsuppressed) + [
            f for f in proto if f.key not in proto_base]
    for f in sorted(unsuppressed, key=lambda x: (x.path, x.line)):
        if args.json:
            print(json.dumps(dataclasses.asdict(f)))
        else:
            print(f.render())
    for k in sorted(stale):
        print(f"[analysis] stale baseline entry (finding no longer "
              f"fires — delete the line): {k}", file=sys.stderr)
    if unsuppressed or stale:
        print(f"[analysis] FAIL: {len(unsuppressed)} unsuppressed "
              f"finding(s), {len(stale)} stale baseline entr(ies)",
              file=sys.stderr)
        return 1
    print("[analysis] clean: 0 unsuppressed findings")
    return 0


if __name__ == "__main__":
    sys.exit(main())
