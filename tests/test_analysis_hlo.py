"""Compiled-graph auditor tests (apex_tpu.analysis.hlo).

Per-rule synthetic fixtures — a knowably-donatable jit, a deliberate
bf16->f32 upcast, a psum added to a shard_map body, a forced host
callback — each asserting the exact rule and provenance, plus the
repo self-check: the committed tools/hlo_baseline.json must be
current against fresh lowerings of every registered entry point
(the conftest provides the 8-device host-platform mesh the multichip
entries need, same as tools/ci.sh step 7).
"""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.analysis import hlo
from apex_tpu.testing import entry_points as eps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entry(name, build, **kw):
    return eps.EntryPoint(name=name, build=build, **kw)


def _audit(ep):
    from pathlib import Path

    return hlo._audit_one(ep.name, ep, Path(REPO))


# ---------------------------------------------------------------------------
# APX601 — missed donation
# ---------------------------------------------------------------------------

class TestDonation:
    def _build(self, donate):
        x = jnp.arange(4096, dtype=jnp.float32)

        def step(x):
            return x * 1.5 + 1.0

        fn = (functools.partial(jax.jit, donate_argnums=(0,))(step)
              if donate else jax.jit(step))
        return fn, (x,)

    def test_undonated_dead_arg_fires_apx601(self):
        ep = _entry("fixture_undonated",
                    lambda: self._build(donate=False), dead_args=(0,))
        audit = _audit(ep)
        rules = [f.rule for f in audit.findings]
        assert rules == ["APX601"]
        f = audit.findings[0]
        assert f.symbol == "arg0"
        assert "16384 bytes" in f.message
        assert audit.donated == {}

    def test_donated_arg_is_clean(self):
        ep = _entry("fixture_donated",
                    lambda: self._build(donate=True), dead_args=(0,))
        audit = _audit(ep)
        assert audit.findings == []
        assert 0 in audit.donated

    def test_live_arg_not_flagged(self):
        # same undonated jit, but the registry says the caller keeps
        # the buffer — donation would be wrong, not missing
        ep = _entry("fixture_live",
                    lambda: self._build(donate=False), dead_args=())
        assert _audit(ep).findings == []

    def test_tiny_buffers_ignored(self):
        def build():
            s = jnp.float32(2.0)  # 4 bytes: donation saves nothing
            return jax.jit(lambda s: s * 2.0), (s,)

        ep = _entry("fixture_tiny", build, dead_args=(0,))
        assert _audit(ep).findings == []

    def test_stablehlo_alias_parsing(self):
        fn, args = self._build(donate=True)
        text = fn.lower(*args).as_text()
        assert hlo._donated_args(text) == {0: 0}


# ---------------------------------------------------------------------------
# APX602 — silent dtype promotion
# ---------------------------------------------------------------------------

class TestPromotion:
    def _build_upcast(self):
        x = jnp.ones((256, 128), jnp.bfloat16)

        def f(x):
            y = x.astype(jnp.float32) * 2.0   # the deliberate upcast
            return y.astype(jnp.bfloat16) + x

        return jax.jit(f), (x,)

    def test_deliberate_upcast_fires_apx602_with_provenance(self):
        ep = _entry("fixture_upcast", self._build_upcast, policy="O5")
        audit = _audit(ep)
        apx602 = [f for f in audit.findings if f.rule == "APX602"]
        assert len(apx602) == 1
        f = apx602[0]
        assert f.path == "tests/test_analysis_hlo.py"
        assert f.line > 0
        assert "bfloat16->float32" in f.message
        assert f.symbol.startswith("fixture_upcast.f.")

    def test_policy_gate(self):
        # the same graph under a non-low-precision policy tag is not
        # a promotion hazard — APX602 is an O4/O5 rule
        ep = _entry("fixture_upcast_o2", self._build_upcast,
                    policy="O2")
        assert [f for f in _audit(ep).findings
                if f.rule == "APX602"] == []

    def test_sanctioned_region_exempt(self):
        ep = _entry("fixture_upcast_ok", self._build_upcast,
                    policy="O5",
                    allow_upcast=("tests/test_analysis_hlo.py",))
        assert [f for f in _audit(ep).findings
                if f.rule == "APX602"] == []


# ---------------------------------------------------------------------------
# APX603 — collective census
# ---------------------------------------------------------------------------

class TestCensus:
    def _build_psum(self, with_extra=False):
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu._compat import shard_map

        mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
        x = jnp.ones((64, 128), jnp.float32)

        def body(x):
            y = jax.lax.psum(x, "d")
            if with_extra:
                y = y + jax.lax.all_gather(x, "d").sum(0)
            return y

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("d"),
                               out_specs=P(), check_vma=False))
        return fn, (x,)

    def test_psum_in_shard_map_counted_with_bytes(self):
        ep = _entry("fixture_psum", self._build_psum)
        audit = _audit(ep)
        census = audit.census()
        assert "psum" in census
        assert census["psum"]["count"] == 1
        # per-shard (8, 128) fp32 = 4096 bytes moved per step
        assert census["psum"]["bytes_per_step"] == 8 * 128 * 4
        op = [o for o in audit.collectives if o.kind == "psum"][0]
        assert op.path == "tests/test_analysis_hlo.py"
        assert op.function == "body"

    def test_new_collective_kind_fails_diff(self):
        ep = _entry("fixture_psum2",
                    lambda: self._build_psum(with_extra=True))
        audit = _audit(ep)
        base_row = {"collectives": {"psum": audit.census()["psum"]},
                    "peak_live_bytes": audit.peak_live_bytes}
        findings = hlo._census_findings("fixture_psum2", audit,
                                        base_row)
        kinds = {f.symbol for f in findings if f.rule == "APX603"}
        assert "all_gather.new" in kinds
        new = [f for f in findings if f.symbol == "all_gather.new"][0]
        assert "tests/test_analysis_hlo.py" in new.message  # provenance

    def test_byte_growth_and_shrink_gated_at_10pct(self):
        ep = _entry("fixture_psum3", self._build_psum)
        audit = _audit(ep)
        row = audit.baseline_row()
        ok = json.loads(json.dumps(row))
        ok["collectives"]["psum"]["bytes_per_step"] = int(
            audit.census()["psum"]["bytes_per_step"] / 1.05)  # +5%
        assert [f for f in hlo._census_findings("e", audit, ok)
                if f.rule == "APX603"] == []
        grown = json.loads(json.dumps(row))
        grown["collectives"]["psum"]["bytes_per_step"] = int(
            audit.census()["psum"]["bytes_per_step"] / 1.5)  # +50%
        fs = [f for f in hlo._census_findings("e", audit, grown)
              if f.rule == "APX603"]
        assert any("grew >10%" in f.message for f in fs)
        shrunk = json.loads(json.dumps(row))
        shrunk["collectives"]["psum"]["bytes_per_step"] = int(
            audit.census()["psum"]["bytes_per_step"] * 2)
        fs = [f for f in hlo._census_findings("e", audit, shrunk)
              if f.rule == "APX603"]
        assert any("shrank >10%" in f.message for f in fs)

    def test_scan_body_collectives_priced_per_step(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu._compat import shard_map

        mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
        x = jnp.ones((64, 128), jnp.float32)

        def body(x):
            def it(c, _):
                return c + jax.lax.psum(x, "d"), ()

            out, _ = jax.lax.scan(it, jnp.zeros_like(x), None,
                                  length=5)
            return out

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("d"),
                               out_specs=P("d"), check_vma=False))
        ep = _entry("fixture_scan_psum", lambda: (fn, (x,)))
        census = _audit(ep).census()
        assert census["psum"]["count"] == 5
        assert census["psum"]["bytes_per_step"] == 5 * 8 * 128 * 4


# ---------------------------------------------------------------------------
# APX604 — host transfer in the compiled graph
# ---------------------------------------------------------------------------

class TestHostTransfer:
    def test_io_callback_fires_apx604(self):
        from jax.experimental import io_callback

        x = jnp.ones((128,), jnp.float32)

        def f(x):
            # the forced device->host round trip: XLA services this
            # callback from the host on every execution
            io_callback(lambda a: None, None, x)
            return x * 2.0

        ep = _entry("fixture_callback", lambda: (jax.jit(f), (x,)))
        audit = _audit(ep)
        apx604 = [f for f in audit.findings if f.rule == "APX604"]
        assert len(apx604) == 1
        assert apx604[0].path == "tests/test_analysis_hlo.py"
        assert "io_callback" in apx604[0].message

    def test_debug_print_fires_apx604(self):
        x = jnp.ones((128,), jnp.float32)

        def f(x):
            jax.debug.print("x0 {}", x[0])
            return x * 2.0

        ep = _entry("fixture_debug", lambda: (jax.jit(f), (x,)))
        assert any(f.rule == "APX604" for f in _audit(ep).findings)

    def test_clean_graph_has_no_apx604(self):
        x = jnp.ones((128,), jnp.float32)
        ep = _entry("fixture_clean",
                    lambda: (jax.jit(lambda x: x * 2.0), (x,)))
        assert _audit(ep).findings == []


# ---------------------------------------------------------------------------
# APX605 — peak-live-memory estimate
# ---------------------------------------------------------------------------

class TestPeakMemory:
    def test_known_program_exact_bytes(self):
        # x (4 KiB) live at entry; y = x*2 allocates 4 KiB (peak 8);
        # z = y + x allocates 4 KiB while x and y are still live ->
        # peak 12 KiB
        def f(x):
            y = x * 2.0
            return y + x

        closed = jax.make_jaxpr(f)(jnp.ones((1024,), jnp.float32))
        assert hlo.peak_live_bytes(closed.jaxpr) == 3 * 4096

    def test_freeing_lowers_the_peak(self):
        # a chain frees each intermediate after its single use: peak
        # is input + two live values, never all four
        def chain(x):
            a = x * 2.0
            b = a * 2.0
            c = b * 2.0
            return c

        closed = jax.make_jaxpr(chain)(jnp.ones((1024,), jnp.float32))
        assert hlo.peak_live_bytes(closed.jaxpr) == 2 * 4096

    def test_dropped_outputs_are_not_priced(self):
        # an output nobody reads is a drop-var and is never written:
        # input + the one sorted operand that is kept, not both (jax
        # 0.9.0's backward shard_map drops one output per residual)
        def kept_half(x):
            return jax.lax.sort((x, x), num_keys=1)[0]

        closed = jax.make_jaxpr(kept_half)(jnp.ones((1024,), jnp.float32))
        (eqn,) = closed.jaxpr.eqns
        assert sum(isinstance(o, jax.core.DropVar)
                   for o in eqn.outvars) == 1
        assert hlo.peak_live_bytes(closed.jaxpr) == 2 * 4096

    def test_pjit_inner_peak_counted(self):
        # the same chain jitted: the walk must descend into the pjit
        # call and see the inner liveness, not price the call as one
        # opaque 4 KiB -> 4 KiB op
        @jax.jit
        def inner(x):
            a = x * 2.0
            b = a + x       # x + a + b live -> 12 KiB inside
            return b * 2.0

        closed = jax.make_jaxpr(lambda x: inner(x))(
            jnp.ones((1024,), jnp.float32))
        assert hlo.peak_live_bytes(closed.jaxpr) >= 3 * 4096

    def test_drift_gate(self):
        def f(x):
            return x * 2.0

        ep = _entry("fixture_mem", lambda: (jax.jit(f),
                                            (jnp.ones((1024,)),)))
        audit = _audit(ep)
        row = audit.baseline_row()
        assert hlo._census_findings("e", audit, row) == []
        small = dict(row, peak_live_bytes=row["peak_live_bytes"] // 2)
        fs = hlo._census_findings("e", audit, small)
        assert [f.rule for f in fs] == ["APX605"]
        assert "grew >10%" in fs[0].message
        big = dict(row, peak_live_bytes=row["peak_live_bytes"] * 2)
        fs = hlo._census_findings("e", audit, big)
        assert [f.rule for f in fs] == ["APX605"]
        assert "shrank >10%" in fs[0].message


# ---------------------------------------------------------------------------
# the registry + repo self-check
# ---------------------------------------------------------------------------

class TestRegistryAndSelfCheck:
    def test_every_entry_builds_and_lowers(self):
        avail = eps.available_entry_points()
        # the conftest forces 8 host devices: every entry must be here
        assert set(avail) == set(eps.ENTRY_POINTS)
        assert len(avail) >= 7

    def test_smoke_drivers_share_the_registry_builders(self):
        # the registry's GPT entry and the sanitizer smoke must build
        # through the same function object — one list of lowerable
        # steps, not parallel reconstructions
        import inspect

        from apex_tpu.analysis import sanitizer
        from apex_tpu.testing import standalone_gpt

        src = inspect.getsource(sanitizer.sanitize_smoke)
        assert "make_smoke_setup" in src and "build_train_step" in src
        src = inspect.getsource(standalone_gpt.train_smoke)
        assert "make_smoke_setup" in src and "build_train_step" in src
        src = inspect.getsource(eps._build_gpt_train_step)
        assert "make_smoke_setup" in src and "build_train_step" in src

    def test_repo_hlo_check_is_clean_and_baseline_current(self):
        """The acceptance bar: zero unsuppressed findings on every
        registered entry point against the COMMITTED baselines —
        i.e. the donation/promotion fixes shipped and the census/
        memory rows in tools/hlo_baseline.json are current."""
        unsuppressed, stale, audits = hlo.run_hlo_check(REPO)
        assert unsuppressed == [], "\n".join(
            f.render() for f in unsuppressed)
        assert stale == []
        assert len(audits) >= 7
        # the committed baseline has a row for every audited entry
        base = hlo.load_hlo_baseline(repo_root=REPO)
        assert set(audits) <= set(base["entries"])

    def test_multichip_census_covers_the_parallel_stack(self):
        base = hlo.load_hlo_baseline(repo_root=REPO)["entries"]
        assert "psum" in base["gpt_dp8_train_step"]["collectives"]
        zero = base["zero_dp8_update_step"]["collectives"]
        assert {"all_gather", "reduce_scatter"} <= set(zero)

    def test_train_steps_are_donated_end_to_end(self):
        """The APX601 payoff pinned down: params AND amp state (the
        masters + optimizer-state buffers) carry donation annotations
        in the lowered smoke train steps."""
        fn, args = eps.ENTRY_POINTS["gpt_train_step"].build()
        donated = hlo._donated_args(fn.lower(*args).as_text())
        n_leaves = sum(len(jax.tree_util.tree_leaves(a))
                       for a in args)
        assert len(donated) == n_leaves  # every input buffer donated

    def test_partial_update_preserves_unaudited_baseline_rows(
            self, tmp_path, monkeypatch):
        # --update-hlo-baseline with an --entry filter (or on a host
        # missing the multichip device count) must keep the committed
        # rows it did not re-measure — a partial update deleting 6 of
        # 7 entries would red the next full CI run
        import shutil

        (tmp_path / "tools").mkdir()
        shutil.copy(os.path.join(REPO, "tools", "hlo_baseline.json"),
                    tmp_path / "tools" / "hlo_baseline.json")
        audits = hlo.audit_entry_points(REPO,
                                        names=["gpt_train_step"])
        assert list(audits) == ["gpt_train_step"]
        hlo.write_hlo_baseline(audits, repo_root=str(tmp_path))
        after = hlo.load_hlo_baseline(repo_root=str(tmp_path))
        before = hlo.load_hlo_baseline(repo_root=REPO)
        assert set(after["entries"]) == set(before["entries"])
        assert after["entries"]["zero_dp8_update_step"] == \
            before["entries"]["zero_dp8_update_step"]

    def test_suppressions_for_unaudited_entries_not_stale(
            self, tmp_path):
        # a suppression belonging to a multichip entry must not be
        # reported stale by a filtered (or single-device) invocation
        # that never audited it; unattributable keys only go stale on
        # full runs
        import shutil

        (tmp_path / "tools").mkdir()
        shutil.copy(os.path.join(REPO, "tools", "hlo_baseline.json"),
                    tmp_path / "tools" / "hlo_baseline.json")
        (tmp_path / "tools" / "hlo_findings.txt").write_text(
            "<entry:gpt_dp8_train_step>:APX601:arg3  # hypothetical\n"
            "apex_tpu/x.py:APX602:gpt_dp8_train_step.f.bfloat16"
            "  # hypothetical\n"
            "orphan:APX900:nodots  # unattributable\n")
        _, stale, _ = hlo.run_hlo_check(str(tmp_path),
                                        names=["gpt_train_step"])
        assert stale == []
        # the full run still flags all three (entry audited + no
        # matching finding; orphan judged by full coverage)
        _, stale, audits = hlo.run_hlo_check(str(tmp_path))
        assert set(audits) == set(eps.ENTRY_POINTS)
        assert len(stale) == 3

    def test_cli_entry_typo_is_an_error(self):
        from apex_tpu.analysis.__main__ import main

        with pytest.raises(SystemExit) as e:
            main(["--check-hlo", "--entry", "gpt_tran_step"])
        assert e.value.code == 2  # argparse error, not "hlo clean"

    def test_stale_baseline_entry_fails(self, tmp_path):
        base = hlo.load_hlo_baseline(repo_root=REPO)
        base["entries"]["ghost_entry"] = {"collectives": {},
                                          "peak_live_bytes": 1}
        (tmp_path / "tools").mkdir()
        (tmp_path / "tools" / "hlo_baseline.json").write_text(
            json.dumps(base))
        (tmp_path / "tools" / "hlo_findings.txt").write_text("")
        # lower only the cheapest entry; the stale row still fails
        unsuppressed, _, _ = hlo.run_hlo_check(
            str(tmp_path), names=["fixture_none"])
        stale = [f for f in unsuppressed
                 if f.symbol == "stale-entry"]
        assert len(stale) == 1 and "ghost_entry" in stale[0].message


# ---------------------------------------------------------------------------
# rule registry + CLI surface
# ---------------------------------------------------------------------------

class TestRulesRegistry:
    def test_apx6xx_rules_registered(self):
        from apex_tpu.analysis.rules import RULES

        for rid in ("APX601", "APX602", "APX603", "APX604", "APX605"):
            assert rid in RULES
            assert RULES[rid].layer == "compiled"

    def test_rule_table_covers_linter_and_hlo(self):
        from apex_tpu.analysis.rules import render_rule_table

        table = render_rule_table()
        for rid in ("APX101", "APX301", "APX401", "APX501", "APX601",
                    "APX605", "APX900"):
            assert f"`{rid}`" in table

    def test_duplicate_rule_rejected(self):
        from apex_tpu.analysis.rules import register_rule

        with pytest.raises(ValueError, match="duplicate"):
            register_rule("APX601", "compiled", "x", "y")

    def test_entrypoint_fields_are_frozen_data(self):
        ep = eps.ENTRY_POINTS["gpt_train_step"]
        assert dataclasses.is_dataclass(ep)
        with pytest.raises(dataclasses.FrozenInstanceError):
            ep.policy = "O0"


# ---------------------------------------------------------------------------
# ISSUE-10 regression fixture: the 8-device ZeRO entries under the HLO
# auditor — donation-clean, and the census rows cover BOTH halves of
# the ZeRO exchange (psum_scatter -> reduce_scatter, all_gather) with
# exact provenance, so a refactor that drops either collective (or
# un-donates the state) fails here before it fails on a pod.
# ---------------------------------------------------------------------------

class TestZeroEntriesRegression:
    ZERO_ENTRIES = ("zero_dp8_update_step", "zero_dp8_adam_step")

    @pytest.fixture(scope="class")
    def audits(self):
        return hlo.audit_entry_points(REPO, names=list(
            self.ZERO_ENTRIES))

    def test_donation_clean(self, audits):
        for name in self.ZERO_ENTRIES:
            missed = [f for f in audits[name].findings
                      if f.rule == "APX601"]
            assert missed == [], "\n".join(
                f.render() for f in missed)

    def test_census_covers_scatter_and_gather_with_provenance(
            self, audits):
        for name in self.ZERO_ENTRIES:
            kinds = {op.kind for op in audits[name].collectives}
            assert {"reduce_scatter", "all_gather"} <= kinds, name
        # exact provenance: the update entry's pair lives in its own
        # shard fn; the adam entry's grad scatter + delta gather live
        # in the OPTIMIZER (distributed_fused_adam.update), with the
        # extra rank-derivation scatter priced to the compat shim
        upd = audits["zero_dp8_update_step"].collectives
        assert all(op.path == "apex_tpu/testing/entry_points.py"
                   and op.function == "shard" for op in upd)
        adam = audits["zero_dp8_adam_step"].collectives
        opt = "apex_tpu/contrib/optimizers/distributed_fused_adam.py"
        assert any(op.kind == "reduce_scatter" and op.path == opt
                   and op.function == "update" for op in adam)
        assert all(op.path == opt for op in adam
                   if op.kind == "all_gather")

    def test_committed_baseline_rows_price_both_kinds(self):
        base = hlo.load_hlo_baseline(repo_root=REPO)["entries"]
        for name in self.ZERO_ENTRIES:
            cens = base[name]["collectives"]
            assert {"reduce_scatter", "all_gather"} <= set(cens), name
            for kind in ("reduce_scatter", "all_gather"):
                assert cens[kind]["count"] >= 1
                assert cens[kind]["bytes_per_step"] > 0
        # the adam entry donates params AND every state leaf (the
        # end-to-end requirement: a missed state donation doubles the
        # largest buffers in the step)
        adam = base["zero_dp8_adam_step"]
        n_state_leaves = 3  # count + m[0] + v[0]
        assert len(adam["donated_args"]) >= 2 + n_state_leaves - 1
