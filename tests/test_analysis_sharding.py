"""SPMD sharding auditor (apex_tpu.analysis.sharding) + MeshPlan.

Per-rule synthetic fixtures — one per APX701-705, each proving the
rule FIRES with exact rule id + provenance — plus the acceptance bar:
``run_sharding_check`` green on every planned multichip entry against
the committed ``tools/sharding_baseline.json``, and the
deliberately-reintroduced ZeRO replicated-state bug (the real finding
the auditor turned up at introduction) caught as APX701.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu._compat import shard_map
from apex_tpu.analysis import sharding
from apex_tpu.mesh_plan import MeshAxis, MeshPlan
from apex_tpu.testing import entry_points as eps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device CPU mesh")


def _plan8(**kw):
    return MeshPlan.build(axes=(("zero", 8, "zero"),), **kw)


def _mesh8():
    return _plan8().make_mesh()


# ---------------------------------------------------------------------------
# MeshPlan: the frozen topology contract
# ---------------------------------------------------------------------------

class TestMeshPlan:
    def test_build_and_queries(self):
        plan = MeshPlan.build(
            axes=(("pipe", 2, "pipeline"), ("data", 2, "data"),
                  ("tensor", 2, "tensor")),
            tensor_specs={r"^in0$": ("data", None, "tensor")},
            collective_budget={"psum": 3})
        assert plan.world_size == 8
        assert plan.axis("data").kind == "data"
        assert plan.axes_of_kind("tensor") == (MeshAxis("tensor", 2,
                                                        "tensor"),)
        assert plan.budget() == {"psum": 3}
        assert plan.describe() == \
            "pipe=2(pipeline) x data=2(data) x tensor=2(tensor)"

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown parallelism"):
            MeshPlan.build(axes=(("x", 2, "banana"),))
        with pytest.raises(ValueError, match="duplicate axis"):
            MeshPlan.build(axes=(("x", 2, "data"), ("x", 2, "data")))
        with pytest.raises(ValueError, match="names axis"):
            MeshPlan.build(axes=(("x", 2, "data"),),
                           tensor_specs={"a": ("y",)})

    def test_spec_for_first_match_wins_and_with_specs_prepends(self):
        plan = _plan8(tensor_specs={r"\.m\b": ("zero",), r".": ()})
        assert plan.spec_for("state.m[0]") == ("zero",)
        assert plan.spec_for("state.count") == ()
        special = plan.with_specs({r"state\.m\[0\]": ()})
        assert special.spec_for("state.m[0]") == ()
        assert special.spec_for("state.m[1]") == ("zero",)

    def test_expected_shard_shape_and_divisibility(self):
        plan = _plan8()
        assert plan.expected_shard_shape((64, 16), ("zero",)) == (8, 16)
        assert plan.expected_shard_shape((64, 16), ()) == (64, 16)
        with pytest.raises(ValueError, match="not divisible"):
            plan.expected_shard_shape((63,), ("zero",))
        with pytest.raises(ValueError, match="more dims"):
            plan.expected_shard_shape((8,), ("zero", None))

    def test_json_roundtrip(self):
        plan = MeshPlan.build(
            axes=(("tensor", 2, "tensor"), ("expert", 4, "expert")),
            tensor_specs={r"\['wi'\]": (("tensor", "expert"),),
                          r"\['b'\]": (None, "expert")},
            collective_budget={"all_to_all": 4})
        again = MeshPlan.from_json(
            json.loads(json.dumps(plan.to_json())))
        assert again == plan

    def test_json_roundtrip_preserves_shadowing_override(self):
        """with_specs PREPENDS; a dict-keyed serialization would keep
        the LOSING base spec for a shadowed pattern — the pair-list
        form must round-trip the winner."""
        plan = _plan8(tensor_specs={r"x": ("zero",)}).with_specs(
            {r"x": ()})
        assert plan.spec_for("x") == ()
        again = MeshPlan.from_json(
            json.loads(json.dumps(plan.to_json())))
        assert again == plan
        assert again.spec_for("x") == ()

    def test_partition_spec_and_make_mesh(self):
        plan = _plan8(tensor_specs={r"\.m\b": ("zero",)})
        assert plan.partition_spec("s.m[0]") == P("zero")
        assert plan.partition_spec("undeclared") == P()
        mesh = plan.make_mesh()
        assert mesh.axis_names == ("zero",)
        assert mesh.devices.shape == (8,)

    def test_tensor_paths_naming(self):
        tree = {"a": jnp.zeros((2,)), "b": [jnp.zeros(()),
                                            jnp.zeros((3,))]}
        paths = sharding.tensor_paths(tree, "in0")
        assert paths == ["in0['a']", "in0['b'][0]", "in0['b'][1]"]


# ---------------------------------------------------------------------------
# per-rule synthetic fixtures
# ---------------------------------------------------------------------------

class TestRuleFixtures:
    def test_apx701_replicated_where_plan_shards(self):
        """A 4 KiB tensor the plan shards over 'zero' propagated fully
        replicated: the silent-ZeRO-regression fixture."""
        mesh = _mesh8()
        plan = _plan8(tensor_specs={r"^in0\.m\b": ("zero",)})
        aval = jax.core.ShapedArray((1024,), jnp.float32)
        out = sharding._spec_findings(
            "fx", plan, ["in0.m[0]"], [NamedSharding(mesh, P())],
            [aval], None)
        assert [f.rule for f in out] == ["APX701"]
        assert "fully REPLICATED" in out[0].message
        assert "in0.m[0]" in out[0].message
        assert "(128,)" in out[0].message  # the promised shard shape

    def test_apx701_floor_exempts_scalars(self):
        mesh = _mesh8()
        plan = _plan8(tensor_specs={r"^in0$": ("zero",)})
        aval = jax.core.ShapedArray((8,), jnp.float32)  # 32 bytes
        out = sharding._spec_findings(
            "fx", plan, ["in0"], [NamedSharding(mesh, P())], [aval],
            None)
        assert out == []

    def test_apx703_drift_stale_pattern_and_budget(self):
        mesh = _mesh8()
        # drift: plan says replicated, partitioner sharded it
        plan = _plan8(tensor_specs={r"^in0$": ()})
        aval = jax.core.ShapedArray((64, 4), jnp.float32)
        out = sharding._spec_findings(
            "fx", plan, ["in0"], [NamedSharding(mesh, P("zero"))],
            [aval], None)
        assert [f.rule for f in out] == ["APX703"]
        assert "partitioner assigned" in out[0].message
        # stale pattern: a declared spec matching no audited tensor
        plan2 = _plan8(tensor_specs={r"ghost": ("zero",)})
        out2 = sharding._spec_findings("fx", plan2, ["in0"],
                                       [NamedSharding(mesh, P())],
                                       [aval], None)
        assert [f.rule for f in out2] == ["APX703"]
        assert "matches no audited tensor" in out2[0].message
        # budget: unbudgeted kind + overrun, with op provenance
        def prog(x):
            return shard_map(
                lambda x: jax.lax.psum(
                    jax.lax.psum(x, "zero"), "zero"),
                mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False)(x)

        jaxpr = jax.make_jaxpr(prog)(jnp.ones((8,)))
        census, ops = sharding._collective_census(jaxpr.jaxpr)
        assert census == {"psum": 2}
        plan3 = _plan8(collective_budget={"psum": 1})
        out3 = sharding._budget_findings("fx", plan3, census, ops,
                                         REPO)
        assert [f.rule for f in out3] == ["APX703"]
        assert "exceeds the plan budget: 2" in out3[0].message
        assert "test_analysis_sharding.py" in out3[0].message
        plan4 = _plan8(collective_budget={"all_gather": 1})
        out4 = sharding._budget_findings("fx", plan4, census, ops,
                                         REPO)
        # unbudgeted psum fires; the budgeted-but-unseen all_gather
        # does NOT (the budget is a ceiling, not an exact count)
        assert [f.rule for f in out4] == ["APX703"]
        assert "UNBUDGETED" in out4[0].message

    def test_apx702_gather_then_rescatter_chain(self):
        """all_gather feeding a reduce_scatter of the same operand —
        through a dtype convert — is the wasted-bytes chain."""
        mesh = _mesh8()

        def prog(x):
            def f(x):
                g = jax.lax.all_gather(x, "zero", axis=0, tiled=True)
                g16 = g.astype(jnp.bfloat16)  # pass-through hop
                return jax.lax.psum_scatter(
                    g16.astype(jnp.float32), "zero",
                    scatter_dimension=0, tiled=True)

            return shard_map(f, mesh=mesh, in_specs=P("zero"),
                             out_specs=P("zero"), check_vma=False)(x)

        jaxpr = jax.make_jaxpr(prog)(jnp.ones((64,)))
        errors, _ = sharding._chain_findings("fx", jaxpr.jaxpr, REPO)
        assert [f.rule for f in errors] == ["APX702"]
        msg = errors[0].message
        assert "all_gather" in msg and "reduce_scatter" in msg
        assert "test_analysis_sharding.py" in msg  # both provenances

    def test_apx702_clean_gather_no_finding(self):
        mesh = _mesh8()

        def prog(x):
            return shard_map(
                lambda x: jax.lax.all_gather(x, "zero", axis=0,
                                             tiled=True) * 2.0,
                mesh=mesh, in_specs=P("zero"), out_specs=P(),
                check_vma=False)(x)

        jaxpr = jax.make_jaxpr(prog)(jnp.ones((64,)))
        errors, _ = sharding._chain_findings("fx", jaxpr.jaxpr, REPO)
        assert errors == []

    def test_apx704_non_overlappable_collective(self):
        """The collective's output consumed by the NEXT equation while
        independent compute exists later -> advisory; hoisting the
        independent compute between them -> silence."""
        mesh = _mesh8()

        def tight(x, a):
            def f(x, a):
                g = jax.lax.all_to_all(x, "zero", 0, 0)
                y = g * 2.0             # zero slack after the a2a
                w = a @ a               # independent, could overlap
                return y.sum() + w.sum()

            return shard_map(f, mesh=mesh, in_specs=(P("zero"), P()),
                             out_specs=P(), check_vma=False)(x, a)

        x = jnp.ones((64, 8))  # local (8, 8): a2a splits dim 0 by 8
        a = jnp.ones((4, 4))
        jaxpr = jax.make_jaxpr(tight)(x, a)
        _, advisories = sharding._chain_findings("fx", jaxpr.jaxpr,
                                                 REPO)
        assert [f.rule for f in advisories] == ["APX704"]
        assert "all_to_all" in advisories[0].message
        assert advisories[0].severity == "advisory"

        def hoisted(x, a):
            def f(x, a):
                g = jax.lax.all_to_all(x, "zero", 0, 0)
                w = a @ a               # slack: a2a can overlap this
                y = g * 2.0
                return y.sum() + w.sum()

            return shard_map(f, mesh=mesh, in_specs=(P("zero"), P()),
                             out_specs=P(), check_vma=False)(x, a)

        jaxpr2 = jax.make_jaxpr(hoisted)(x, a)
        _, adv2 = sharding._chain_findings("fx", jaxpr2.jaxpr, REPO)
        assert adv2 == []

    def test_apx704_moe_overlapped_exchange_goes_quiet(self):
        """ISSUE-19 regression: the chunked expert exchange issues
        the dispatch a2a's back-to-back and trails each return a2a
        with the NEXT chunk's expert matmul, so the overlap advisory
        is silent; ``a2a_chunks=1`` restores the legacy single-shot
        trace — expert matmul consuming the dispatch a2a immediately
        — and with it the advisory."""
        from apex_tpu.transformer.expert_parallel import (
            moe_dispatch_combine_fused)

        mesh = _mesh8()
        e, h = 8, 16
        x = jnp.ones((256, h))
        logits = jnp.ones((256, e))
        w = jnp.ones((e, h, h))

        def prog(chunks):
            def f(x, logits, w):
                y, _ = moe_dispatch_combine_fused(
                    x, logits,
                    lambda d: jnp.einsum(
                        "ech,ehf->ecf", d, w,
                        preferred_element_type=jnp.float32),
                    e, capacity_factor=4.0, axis_name="zero",
                    a2a_chunks=chunks)
                return y

            return shard_map(
                f, mesh=mesh,
                in_specs=(P("zero"), P("zero"), P("zero")),
                out_specs=P("zero"), check_vma=False)

        jaxpr = jax.make_jaxpr(prog(2))(x, logits, w)
        _, adv = sharding._chain_findings("fx", jaxpr.jaxpr, REPO)
        assert [f.rule for f in adv] == []

        jaxpr1 = jax.make_jaxpr(prog(1))(x, logits, w)
        _, adv1 = sharding._chain_findings("fx", jaxpr1.jaxpr, REPO)
        assert any(f.rule == "APX704" and "all_to_all" in f.message
                   for f in adv1)

    def test_apx705_memory_gate_and_plan_drift(self):
        plan_json = _plan8().to_json()
        audit = sharding.ShardingAudit(
            name="fx", plan_json=plan_json, per_device_bytes=1000,
            census={}, findings=[], advisories=[])
        row = audit.baseline_row()
        # within +/-10%: silent
        assert sharding._baseline_findings(
            "fx", audit, dict(row, per_device_bytes=950)) == []
        grew = sharding._baseline_findings(
            "fx", audit, dict(row, per_device_bytes=800))
        assert [f.rule for f in grew] == ["APX705"]
        assert "grew >10%" in grew[0].message
        shrank = sharding._baseline_findings(
            "fx", audit, dict(row, per_device_bytes=1200))
        assert [f.rule for f in shrank] == ["APX705"]
        assert "shrank >10%" in shrank[0].message
        missing = sharding._baseline_findings("fx", audit, None)
        assert [f.rule for f in missing] == ["APX705"]
        assert "no committed sharding-baseline row" in \
            missing[0].message
        other = dict(row)
        other["plan"] = _plan8(
            collective_budget={"psum": 1}).to_json()
        drift = sharding._baseline_findings("fx", audit, other)
        assert [f.rule for f in drift] == ["APX703"]
        assert "MeshPlan changed" in drift[0].message


# ---------------------------------------------------------------------------
# the real bug, reintroduced: replicated ZeRO state -> APX701
# ---------------------------------------------------------------------------

class TestZeroRegressionCaught:
    def test_replicated_state_boundary_fires_apx701(self):
        """Rebuild the zero_dp8_adam_step with the exact bug the SPMD
        auditor shipped against (a driver carried the ZeRO state
        through its shard_map boundary as P()): the m/v buffers come
        out shard-sized-but-replicated and APX701 names them."""
        from apex_tpu.contrib.optimizers import (
            distributed_fused_adam, zero_adam_plan)

        plan = zero_adam_plan(8, axis_name="zero")
        mesh = plan.make_mesh()
        params = {"w": jnp.ones((512, 16), jnp.float32)}
        grads = {"w": jnp.full((512, 16), 1e-3, jnp.float32)}
        tx = distributed_fused_adam(1e-2, axis_name="zero",
                                    use_pallas=False)
        # THE BUG: out_specs/in_specs P() — each device's 1/8 state
        # shard presented as a replicated global
        state = shard_map(tx.init, mesh=mesh, in_specs=P(),
                          out_specs=P(), check_vma=False)(params)

        def step(p, s, g):
            def shard(p, s, g):
                import optax

                u, s2 = tx.update(g, s, p)
                return optax.apply_updates(p, u), s2

            return shard_map(shard, mesh=mesh,
                             in_specs=(P(), P(), P()),
                             out_specs=(P(), P()),
                             check_vma=False)(p, s, g)

        ep = eps.EntryPoint(
            name="zero_bugged", plan=lambda: plan,
            build=lambda: (jax.jit(step), (params, state, grads)))
        audit = sharding._audit_one("zero_bugged", ep, REPO)
        fired = {f.rule for f in audit.findings}
        assert "APX701" in fired, "\n".join(
            f.render() for f in audit.findings)
        msgs = [f.message for f in audit.findings
                if f.rule == "APX701"]
        assert any(".m[0]" in m for m in msgs)
        assert any(".v[0]" in m for m in msgs)


# ---------------------------------------------------------------------------
# acceptance: the committed repo state is green
# ---------------------------------------------------------------------------

class TestRepoSharded:
    def test_every_planned_entry_audits_clean_vs_baseline(self):
        unsuppressed, advisories, stale, audits = \
            sharding.run_sharding_check(REPO)
        assert unsuppressed == [], "\n".join(
            f.render() for f in unsuppressed)
        assert stale == []
        assert {"gpt_dp8_train_step", "zero_dp8_update_step",
                "zero_dp8_adam_step", "moe_ep8_train_step"} \
            <= set(audits)
        # ISSUE-19 closed ROADMAP item 3's a2a/compute overlap: the
        # chunked expert exchange leaves the MoE entry advisory-free
        # (the legacy a2a_chunks=1 fixture above still fires it)
        assert not any(f.rule == "APX704" and "moe_ep8" in f.message
                       for f in advisories)

    def test_baseline_commits_the_plans(self):
        base = sharding.load_sharding_baseline(repo_root=REPO)
        row = base["entries"]["zero_dp8_adam_step"]
        axes = row["plan"]["axes"]
        assert axes == [{"kind": "zero", "name": "zero", "size": 8}]
        assert [r"\.(m|v)\b", ["zero"]] in row["plan"]["tensor_specs"]
        assert {"reduce_scatter", "all_gather"} <= \
            set(row["collectives"])

    def test_zero_adam_state_is_really_sharded(self):
        """The positive twin of the bug fixture: the registered entry
        compiles with m/v propagated P('zero') — per-device 1/8."""
        ep = eps.ENTRY_POINTS["zero_dp8_adam_step"]
        fn, args = ep.build()
        compiled = fn.lower(*args).compile()
        in_paths = sharding._arg_paths(args)
        shardings = sharding._flatten_shardings(
            compiled.input_shardings[0])
        by_path = dict(zip(in_paths, shardings))
        m_global = jax.tree_util.tree_leaves(args[1].m)[0]
        m_sh = by_path["in1.m[0]"]
        assert m_sh.shard_shape(m_global.shape)[0] == \
            m_global.shape[0] // 8

    def test_partial_update_preserves_unaudited_rows(self, tmp_path):
        import shutil

        (tmp_path / "tools").mkdir()
        shutil.copy(os.path.join(REPO, "tools",
                                 "sharding_baseline.json"),
                    tmp_path / "tools" / "sharding_baseline.json")
        audits = sharding.audit_sharding(
            REPO, names=["zero_dp8_update_step"])
        assert list(audits) == ["zero_dp8_update_step"]
        sharding.write_sharding_baseline(audits,
                                         repo_root=str(tmp_path))
        after = sharding.load_sharding_baseline(
            repo_root=str(tmp_path))
        before = sharding.load_sharding_baseline(repo_root=REPO)
        assert set(after["entries"]) == set(before["entries"])
        assert after["entries"]["moe_ep8_train_step"] == \
            before["entries"]["moe_ep8_train_step"]

    def test_filtered_run_does_not_stale_other_suppressions(
            self, tmp_path):
        import shutil

        (tmp_path / "tools").mkdir()
        shutil.copy(os.path.join(REPO, "tools",
                                 "sharding_baseline.json"),
                    tmp_path / "tools" / "sharding_baseline.json")
        (tmp_path / "tools" / "sharding_findings.txt").write_text(
            "<entry:moe_ep8_train_step>:APX703:budget.psum.over"
            "  # hypothetical\n")
        # audits restricted to the zero entry: the moe suppression is
        # not judged; but the restricted entry's own keys are
        unsuppressed, _, stale, _ = sharding.run_sharding_check(
            str(tmp_path), names=["zero_dp8_update_step"])
        assert stale == []

    def test_cli_check_sharding_green(self):
        from apex_tpu.analysis.__main__ import main

        assert main(["--check-sharding", "--root", REPO]) == 0

    def test_suppression_entry_parses_entry_prefixed_keys(self):
        # the path itself contains a colon — a naive split(":") read
        # "<entry" and attributed dot-less symbols to no entry
        assert sharding._suppression_entry(
            "<entry:zero_dp8_adam_step>:APX705:per-device-mem") == \
            "zero_dp8_adam_step"
        assert sharding._suppression_entry(
            "apex_tpu/x.py:APX702:moe_ep8_train_step.f.all_gather") \
            == "moe_ep8_train_step"
        assert sharding._suppression_entry(
            "orphan:APX900:nodots") is None


# ---------------------------------------------------------------------------
# satellites: linter --paths fast path; multichip topology column
# ---------------------------------------------------------------------------

class TestPathsFilter:
    def test_filtered_lint_scopes_rules_like_the_full_walk(self,
                                                           tmp_path):
        from apex_tpu.analysis import linter

        pkg = tmp_path / "apex_tpu"
        pkg.mkdir()
        # package file: full rule set (broad except -> APX202)
        (pkg / "mod.py").write_text(
            "try:\n    x = 1\nexcept Exception:\n    pass\n")
        # compat-scope file: APX501 only (the except is NOT reported)
        tests_dir = tmp_path / "tests"
        tests_dir.mkdir()
        (tests_dir / "t.py").write_text(
            "from jax.experimental.shard_map import shard_map\n"
            "try:\n    x = 1\nexcept Exception:\n    pass\n")
        # outside both: not lint surface
        (tmp_path / "scratch.py").write_text("import os\n")
        out = linter.lint_paths(
            repo_root=str(tmp_path),
            paths=["apex_tpu/mod.py", "tests/t.py", "scratch.py",
                   "deleted.py"])
        rules = sorted((f.path, f.rule) for f in out)
        assert rules == [("apex_tpu/mod.py", "APX202"),
                         ("tests/t.py", "APX501")]

    def test_filtered_run_check_skips_staleness(self, tmp_path):
        from apex_tpu.analysis import linter

        (tmp_path / "apex_tpu").mkdir()
        (tmp_path / "apex_tpu" / "ok.py").write_text("x = 1\n")
        (tmp_path / "tools").mkdir()
        (tmp_path / "tools" / "analysis_baseline.txt").write_text(
            "apex_tpu/gone.py:APX202:f  # old\n")
        unsuppressed, stale = linter.run_check(
            repo_root=str(tmp_path), paths=["apex_tpu/ok.py"])
        assert unsuppressed == [] and stale == []
        # the full walk DOES judge it stale
        _, stale_full = linter.run_check(repo_root=str(tmp_path))
        assert stale_full == ["apex_tpu/gone.py:APX202:f"]

    def test_repo_paths_fast_path_matches_full_walk_subset(self):
        from apex_tpu.analysis import linter

        target = "apex_tpu/analysis/sharding.py"
        fast = linter.lint_paths(repo_root=REPO, paths=[target])
        full = [f for f in linter.lint_paths(repo_root=REPO)
                if f.path == target]
        assert sorted(f.key for f in fast) == \
            sorted(f.key for f in full)


class TestTopologyColumn:
    def test_plans_match_committed_topology_file(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "graft_entry", os.path.join(REPO, "__graft_entry__.py"))
        graft = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(graft)
        payload = graft._plans_payload(8)
        with open(os.path.join(REPO, "MULTICHIP_TOPOLOGY.json")) as f:
            committed = json.load(f)
        assert payload == committed
        assert committed["legs"]["gpt_3d"]["describe"] == \
            "pipe=2(pipeline) x data=2(data) x tensor=2(tensor)"
        assert committed["legs"]["zero_adam"]["describe"] == \
            "data=8(zero)"

    def test_dryrun_prints_one_plan_line_per_leg(self):
        """The dryrun's stdout contract: sorted '[dryrun] plan <leg>: <axes>' lines derived from the
        canonical constructors (no subprocess — the print loop's
        source of truth is multichip_plans, asserted directly)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "graft_entry", os.path.join(REPO, "__graft_entry__.py"))
        graft = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(graft)
        plans = graft.multichip_plans(8)
        assert set(plans) == {
            "gpt_3d", "interleaved_pp", "sequence_ring", "ulysses",
            "expert_parallel", "tp_x_ep", "zero_adam", "resnet_dp",
            "serving_tp", "serving_ep"}
        for plan in plans.values():
            assert plan.axes  # every leg records real axes
        # kinds cover the full parallelism alphabet
        kinds = {a.kind for p in plans.values() for a in p.axes}
        assert kinds == {"data", "tensor", "pipeline", "sequence",
                         "expert", "zero"}


# ---------------------------------------------------------------------------
# MeshPlan adoption in the parallel stack
# ---------------------------------------------------------------------------

class TestPlanAdoption:
    def test_parallel_state_registers_a_plan(self):
        from apex_tpu import parallel_state

        parallel_state.destroy_model_parallel()
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2,
            pipeline_model_parallel_size=2)
        plan = parallel_state.get_mesh_plan()
        assert plan.describe() == \
            "pipe=2(pipeline) x data=2(data) x tensor=2(tensor)"
        assert plan.make_mesh().shape == \
            dict(parallel_state.get_mesh().shape)

    def test_layer_plans_price_their_collectives(self):
        from apex_tpu.transformer.expert_parallel import (
            ExpertParallelMLP)
        from apex_tpu.transformer.pipeline_parallel import (
            pipeline_plan)
        from apex_tpu.transformer.sequence_parallel import (
            SequenceParallelTransformerLayer)

        # 2 a2a hops per capacity chunk, x2 for the backward
        # transposes (default APEX_TPU_MOE_A2A_CHUNKS=2 -> 8)
        ep = ExpertParallelMLP(16, 32, num_experts=8).mesh_plan(4)
        assert ep.budget() == {"all_to_all": 8}
        ep1 = ExpertParallelMLP(16, 32, num_experts=8,
                                a2a_chunks=1).mesh_plan(4)
        assert ep1.budget() == {"all_to_all": 4}
        assert ep.spec_for("in0['wi']") == ("expert",)
        assert ep.spec_for("in0['router']") == ()
        ring = SequenceParallelTransformerLayer(
            16, 4, causal=True).mesh_plan(4)
        assert ring.budget() == {"ppermute": 12}  # 2*(P-1)*2
        uly = SequenceParallelTransformerLayer(
            16, 4, causal=True, mode="ulysses").mesh_plan(4)
        assert uly.budget() == {"all_to_all": 8}
        pp = pipeline_plan(4, 8)
        assert pp.budget() == {"ppermute": 22}  # (8+4-1) ticks x2
        vpp = pipeline_plan(4, 4, virtual_pipeline_size=2)
        assert vpp.budget() == {"ppermute": 44}  # 11 ticks x2 hops x2

    def test_plan_axis_name_mismatch_raises(self):
        from apex_tpu.transformer.expert_parallel import (
            ExpertParallelMLP)

        plan = MeshPlan.build(axes=(("ep", 4, "expert"),))
        layer = ExpertParallelMLP(16, 32, num_experts=4, plan=plan)
        assert layer.axis_name == "ep"
        with pytest.raises(ValueError, match="expert axis"):
            ExpertParallelMLP(16, 32, num_experts=4, plan=plan,
                              axis_name="other")

    def test_finding_is_dataclass_renderable(self):
        # the Finding plumbing --json uses
        plan = _plan8()
        audit = sharding.ShardingAudit(
            name="fx", plan_json=plan.to_json(),
            per_device_bytes=None, census={}, findings=[],
            advisories=[])
        row = audit.baseline_row()
        assert dataclasses.asdict(audit)["name"] == "fx"
        assert row["per_device_bytes"] is None
