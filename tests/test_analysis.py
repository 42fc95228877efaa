"""apex_tpu.analysis: linter rule fixtures, registry round-trip,
parity audit, sanitizer (recompile + transfer), self-hosted check."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.analysis import flags as flags_mod
from apex_tpu.analysis import linter, parity, sanitizer
from apex_tpu.analysis.linter import lint_source


def _lint(src, path="fixture.py"):
    return lint_source(textwrap.dedent(src), path)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# linter rule fixtures: one known violation per rule class, right line
# ---------------------------------------------------------------------------

class TestLinterRules:
    def test_apx101_host_sync_in_jit(self):
        fs = _lint("""
            import jax

            @jax.jit
            def step(x):
                y = x * 2
                return float(y)
        """)
        assert _rules(fs) == ["APX101"]
        assert fs[0].line == 7
        assert "float()" in fs[0].message

    def test_apx101_item_call(self):
        fs = _lint("""
            import jax

            def body(c, x):
                return c, x.item()

            def run(xs):
                import jax.lax as lax
                return lax.scan(body, 0, xs)
        """)
        assert _rules(fs) == ["APX101"]
        assert fs[0].line == 5

    def test_apx101_np_asarray(self):
        fs = _lint("""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                return np.asarray(x).sum()
        """)
        assert _rules(fs) == ["APX101"]
        assert fs[0].line == 7

    def test_apx102_truthiness_on_tracer(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)
        assert _rules(fs) == ["APX102"]
        assert fs[0].line == 6

    def test_apx102_assert_on_tracer(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                assert x.sum() > 0
                return x
        """)
        # x.sum() is a non-jnp call: laundered -> no finding on the
        # call, but jnp.sum keeps taint:
        fs2 = _lint("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                assert jnp.sum(x) > 0
                return x
        """)
        assert _rules(fs2) == ["APX102"]
        assert fs2[0].line == 7

    def test_apx102_is_none_is_exempt(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x, y):
                if y is None:
                    return x
                return x + y
        """)
        assert fs == []

    def test_apx102_store_index_names_are_not_assigned(self):
        """``ref[0, lo:hi] = traced`` writes ``ref``; ``lo`` and ``hi``
        are read, and a static branch on them stays legal (the row
        chunks of the flash kernels)."""
        fs = _lint("""
            import functools
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def kernel(n, x_ref, o_ref):
                for lo in range(0, n, 8):
                    hi = lo + 8
                    o_ref[0, lo:hi] = jnp.exp(x_ref[0, lo:hi])
                    if hi == n:
                        o_ref[1, :] = jnp.zeros((n,))

            def f(x):
                return pl.pallas_call(functools.partial(kernel, 16),
                                      out_shape=x)(x)
        """)
        assert fs == []

    def test_apx102_shape_branch_is_exempt(self):
        fs = _lint("""
            import jax

            @jax.jit
            def f(x):
                if x.shape[0] > 4:
                    return x[:4]
                return x
        """)
        assert fs == []

    def test_apx103_env_read_in_traced_code(self):
        fs = _lint("""
            import os
            import jax

            @jax.jit
            def f(x):
                if os.environ.get("APEX_TPU_FOO") == "1":
                    return x * 2
                return x
        """)
        assert "APX103" in _rules(fs)
        apx103 = [f for f in fs if f.rule == "APX103"][0]
        assert apx103.line == 7
        assert "APEX_TPU_FOO" in apx103.symbol

    def test_apx201_bare_except(self):
        fs = _lint("""
            def f():
                try:
                    return 1
                except:
                    return 2
        """)
        assert _rules(fs) == ["APX201"]
        assert fs[0].line == 5

    def test_apx202_broad_except_swallow(self):
        fs = _lint("""
            def f():
                try:
                    return 1
                except Exception:
                    return 2
        """)
        assert _rules(fs) == ["APX202"]
        assert fs[0].line == 5

    def test_apx202_reraise_is_clean(self):
        fs = _lint("""
            def f(t):
                try:
                    return 1
                except Exception:
                    t.stop()
                    raise
        """)
        assert fs == []

    def test_apx202_logging_is_clean(self):
        fs = _lint("""
            def f(logger):
                try:
                    return 1
                except Exception as e:
                    logger.warning("boom: %s", e)
                    return 2
        """)
        assert fs == []

    def test_apx301_env_read_outside_registry(self):
        fs = _lint("""
            import os

            LIMIT = int(os.environ.get("APEX_TPU_LIMIT", "4"))
        """)
        assert _rules(fs) == ["APX301"]
        assert fs[0].line == 4
        assert fs[0].symbol == "APEX_TPU_LIMIT"

    def test_apx301_subscript_read(self):
        fs = _lint("""
            import os

            ADDR = os.environ["MASTER_ADDR"]
        """)
        assert _rules(fs) == ["APX301"]

    def test_apx301_exempt_in_flags_module(self):
        fs = lint_source(
            "import os\nV = os.environ.get('APEX_TPU_X')\n",
            "apex_tpu/analysis/flags.py", flags_module=True)
        assert fs == []

    def test_apx501_direct_shard_map(self):
        fs = _lint("""
            import jax

            def f(g, mesh, spec):
                return jax.shard_map(g, mesh=mesh, in_specs=spec,
                                     out_specs=spec)
        """)
        assert _rules(fs) == ["APX501"]
        assert fs[0].line == 5

    def test_apx501_import_form(self):
        fs = _lint("""
            from jax.experimental.shard_map import shard_map
        """)
        assert _rules(fs) == ["APX501"]

    def test_apx900_suppression_without_reason(self):
        fs = _lint("""
            def f():
                try:
                    return 1
                except Exception:  # apex-lint: disable=APX202
                    return 2
        """)
        assert sorted(_rules(fs)) == ["APX202", "APX900"]

    def test_inline_suppression_with_reason(self):
        fs = _lint("""
            def f():
                try:
                    return 1
                except Exception:  # apex-lint: disable=APX202 -- fixture says so
                    return 2
        """)
        assert fs == []

    def test_clean_fixture_zero_findings(self):
        fs = _lint("""
            import jax
            import jax.numpy as jnp

            from apex_tpu.analysis.flags import flag_int

            @jax.jit
            def step(x, y):
                z = jnp.where(x > 0, x, -x)
                return z + y

            def host_side(arr):
                n = int(arr.shape[0])
                if n > 4:
                    return float(n)
                try:
                    return 0.0
                except ValueError:
                    return -1.0
        """)
        assert fs == []

    def test_partial_bound_args_are_static(self):
        # the pallas kernel idiom: config prefix via functools.partial
        fs = _lint("""
            import functools
            import jax
            from jax.experimental import pallas as pl

            def kern(causal, scale, x_ref, o_ref):
                if causal:
                    o_ref[...] = x_ref[...] * scale
                else:
                    o_ref[...] = x_ref[...]

            def call(x, causal):
                return pl.pallas_call(
                    functools.partial(kern, causal, 2.0),
                    out_shape=x)(x)
        """)
        assert fs == []

    def test_syntax_error_reported(self):
        fs = lint_source("def f(:\n", "broken.py")
        assert _rules(fs) == ["APX000"]


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

class TestBaseline:
    def test_baseline_roundtrip(self, tmp_path):
        f = linter.Finding(path="a.py", line=3, col=0, rule="APX201",
                           severity="error", message="m", symbol="s")
        linter.write_baseline([f], "base.txt", repo_root=str(tmp_path))
        loaded = linter.load_baseline("base.txt", repo_root=str(tmp_path))
        assert f.key in loaded

    def test_missing_baseline_is_empty(self, tmp_path):
        assert linter.load_baseline("nope.txt",
                                    repo_root=str(tmp_path)) == {}


# ---------------------------------------------------------------------------
# env-flag registry
# ---------------------------------------------------------------------------

class TestFlagRegistry:
    def test_defaults_roundtrip(self, monkeypatch):
        monkeypatch.delenv("APEX_TPU_PIPELINE_PALLAS", raising=False)
        assert flags_mod.flag_bool("APEX_TPU_PIPELINE_PALLAS") is False
        monkeypatch.delenv("APEX_TPU_STEP_PALLAS_MIN", raising=False)
        assert flags_mod.flag_int("APEX_TPU_STEP_PALLAS_MIN") == 0
        monkeypatch.delenv("APEX_TPU_MONITOR_STALL_S", raising=False)
        assert flags_mod.flag_float("APEX_TPU_MONITOR_STALL_S") == 300.0
        monkeypatch.delenv("APEX_TPU_MONITOR_JSONL", raising=False)
        assert flags_mod.flag_str("APEX_TPU_MONITOR_JSONL") is None

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_PIPELINE_PALLAS", "1")
        assert flags_mod.flag_bool("APEX_TPU_PIPELINE_PALLAS") is True
        monkeypatch.setenv("APEX_TPU_STEP_PALLAS_MIN", "4096")
        assert flags_mod.flag_int("APEX_TPU_STEP_PALLAS_MIN") == 4096

    def test_malformed_int_raises_with_flag_name(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_STEP_PALLAS_MIN", "abc")
        with pytest.raises(ValueError, match="APEX_TPU_STEP_PALLAS_MIN"):
            flags_mod.flag_int("APEX_TPU_STEP_PALLAS_MIN")

    def test_malformed_bool_raises(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_FLASH_PACK_D64", "maybe")
        with pytest.raises(ValueError, match="not a boolean"):
            flags_mod.flag_bool("APEX_TPU_FLASH_PACK_D64")

    def test_range_and_multiple_constraints(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_FLASH_E_BLOCK", "100")
        with pytest.raises(ValueError, match="below minimum"):
            flags_mod.flag_int("APEX_TPU_FLASH_E_BLOCK")
        monkeypatch.setenv("APEX_TPU_FLASH_E_BLOCK", "200")
        with pytest.raises(ValueError, match="multiple of 128"):
            flags_mod.flag_int("APEX_TPU_FLASH_E_BLOCK")
        monkeypatch.setenv("APEX_TPU_FLASH_E_BLOCK", "256")
        assert flags_mod.flag_int("APEX_TPU_FLASH_E_BLOCK") == 256

    def test_unregistered_flag_raises(self):
        with pytest.raises(KeyError, match="not a registered"):
            flags_mod.flag_value("APEX_TPU_NO_SUCH_FLAG")

    def test_kind_mismatch_raises(self):
        with pytest.raises(TypeError, match="bool flag"):
            flags_mod.flag_int("APEX_TPU_PIPELINE_PALLAS")

    def test_consumer_reads_per_call(self, monkeypatch):
        from apex_tpu.ops import fused_pipeline

        monkeypatch.setenv("APEX_TPU_PIPELINE_PALLAS", "0")
        assert fused_pipeline.use_pallas_pipeline() is False
        monkeypatch.setenv("APEX_TPU_PIPELINE_PALLAS", "1")
        assert fused_pipeline.use_pallas_pipeline() is True

    def test_table_lists_every_flag(self):
        table = flags_mod.render_flag_table()
        for name in flags_mod.FLAGS:
            assert f"`{name}`" in table


# ---------------------------------------------------------------------------
# kernel-parity audit
# ---------------------------------------------------------------------------

class TestParityAudit:
    def test_repo_sites_all_registered(self):
        assert parity.audit_kernel_parity(repo_root=".") == []

    def test_every_pallas_site_found(self):
        from pathlib import Path

        sites = parity.pallas_call_sites(Path("apex_tpu/ops"))
        mods = {m for m, _, _ in sites}
        assert {"flash_attention.py", "layer_norm.py",
                "scaled_softmax.py", "fused_optim.py",
                "fused_pipeline.py"} <= mods
        for module, fn, _ in sites:
            assert (module, fn) in parity.KERNEL_TWINS, \
                f"unregistered kernel site {module}:{fn}"

    def test_unregistered_site_detected(self, tmp_path):
        ops = tmp_path / "apex_tpu" / "ops"
        ops.mkdir(parents=True)
        (ops / "rogue.py").write_text(textwrap.dedent("""
            from jax.experimental import pallas as pl

            def rogue_kernel_call(x):
                return pl.pallas_call(lambda x_ref, o_ref: None,
                                      out_shape=x)(x)
        """))
        fs = parity.audit_kernel_parity(repo_root=str(tmp_path))
        assert [f.rule for f in fs] == ["APX401"]
        assert "rogue_kernel_call" in fs[0].message


# ---------------------------------------------------------------------------
# sanitizer
# ---------------------------------------------------------------------------

class TestSanitizer:
    def test_catches_injected_per_step_recompile(self):
        """Shape-varying toy step: every step retraces -> the budget
        trips at the first post-warmup boundary."""

        @jax.jit
        def step(x):
            return x * 2.0

        with pytest.raises(sanitizer.RecompileBudgetExceeded) as ei:
            with sanitizer.sanitize(transfer_guard=None,
                                    recompile_budget=0,
                                    warmup_steps=1) as san:
                for n in range(2, 6):   # a new shape every step
                    step(jnp.ones((n,))).block_until_ready()
                    san.step()
        assert ei.value.names, "offending computations must be named"

    def test_stable_step_passes(self):
        @jax.jit
        def step(x):
            return x * 2.0

        with sanitizer.sanitize(transfer_guard=None, recompile_budget=0,
                                warmup_steps=1) as san:
            for _ in range(4):
                step(jnp.ones((8,))).block_until_ready()
                san.step()
        assert san.post_warmup_compiles == []
        assert len(san.warmup_compiles) >= 1

    def test_catches_injected_host_transfer(self):
        """An implicit device->host transfer inside the sanitized body
        raises via jax's transfer guard."""
        x = jnp.ones((4,))
        with pytest.raises(Exception, match="[Dd]isallowed.*transfer|transfer.*guard|host"):
            with sanitizer.sanitize(transfer_guard="disallow",
                                    recompile_budget=8,
                                    warmup_steps=0):
                float(x[0])  # implicit transfer

    def test_budget_allows_slack(self):
        @jax.jit
        def step(x):
            return x + 1

        with sanitizer.sanitize(transfer_guard=None, recompile_budget=1,
                                warmup_steps=1) as san:
            step(jnp.ones((2,))).block_until_ready()
            san.step()
            step(jnp.ones((3,))).block_until_ready()  # 1 recompile: ok
            san.step()
        assert len(san.post_warmup_compiles) == 1

    def test_log_compiles_restored(self):
        prior = jax.config.jax_log_compiles
        with sanitizer.sanitize(transfer_guard=None) as san:
            del san
        assert jax.config.jax_log_compiles == prior


# ---------------------------------------------------------------------------
# self-hosted: the repo itself is clean, CLI exit codes work
# ---------------------------------------------------------------------------

class TestSelfHosted:
    def test_repo_check_is_clean(self):
        unsuppressed, stale = linter.run_check(repo_root=".")
        assert unsuppressed == [], "\n".join(
            f.render() for f in unsuppressed)
        assert stale == []

    @pytest.mark.slow
    def test_cli_check_exit_zero(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis", "--check"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_docs_generated_tables_current(self):
        # every generated docs table (ops.md flag table, analysis.md
        # APX rule table) must match its registry byte-for-byte
        from apex_tpu.analysis.__main__ import DOCS_TABLES

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for doc, begin, end, render in DOCS_TABLES:
            text = open(os.path.join(root, doc)).read()
            a = text.index(begin) + len(begin)
            b = text.index(end)
            assert text[a:b] == "\n" + render() + "\n", \
                f"{doc}: run python -m apex_tpu.analysis --write-docs"


# ---------------------------------------------------------------------------
# regressions from review
# ---------------------------------------------------------------------------

class TestReviewRegressions:
    def test_apx202_tuple_form_flagged(self):
        fs = _lint("""
            def f():
                try:
                    return 1
                except (ValueError, Exception):
                    return 2
        """)
        assert _rules(fs) == ["APX202"]

    def test_finish_catches_final_step_recompile(self):
        """A recompile in the LAST step (no trailing san.step()) must
        still trip the budget via finish() on context exit."""
        with pytest.raises(sanitizer.RecompileBudgetExceeded):
            with sanitizer.sanitize(transfer_guard=None,
                                    recompile_budget=0,
                                    warmup_steps=1) as san:
                jax.jit(lambda v: v * 3)(jnp.ones((4,))
                                         ).block_until_ready()
                san.step()
                # post-warmup step recompiles, loop ends immediately
                jax.jit(lambda v: v * 3)(jnp.ones((5,))
                                         ).block_until_ready()

    def test_env_read_in_trace_reports_once(self):
        fs = _lint("""
            import os
            import jax

            @jax.jit
            def f(x):
                if os.environ.get("APEX_TPU_FOO") == "1":
                    return x * 2
                return x
        """)
        env_rules = [f.rule for f in fs if "APEX_TPU_FOO" in f.symbol]
        assert env_rules == ["APX103"], env_rules

    def test_apx501_enforced_in_tests_tree(self, tmp_path):
        (tmp_path / "apex_tpu").mkdir()
        (tmp_path / "apex_tpu" / "__init__.py").write_text("")
        (tmp_path / "tests").mkdir()
        (tmp_path / "tests" / "test_x.py").write_text(
            "import jax\n"
            "def test_y(mesh, spec):\n"
            "    jax.shard_map(lambda v: v, mesh=mesh,\n"
            "                  in_specs=spec, out_specs=spec)\n")
        fs = linter.lint_paths(repo_root=str(tmp_path))
        assert [f.rule for f in fs] == ["APX501"]
        assert fs[0].path == "tests/test_x.py"

    def test_parity_walk_reaches_class_methods(self, tmp_path):
        ops = tmp_path / "apex_tpu" / "ops"
        ops.mkdir(parents=True)
        (ops / "clsy.py").write_text(textwrap.dedent("""
            from jax.experimental import pallas as pl

            class Runner:
                def go(self, x):
                    return pl.pallas_call(lambda i, o: None,
                                          out_shape=x)(x)
        """))
        fs = parity.audit_kernel_parity(repo_root=str(tmp_path))
        assert [f.rule for f in fs] == ["APX401"]
        assert "'go'" in fs[0].message

    def test_update_baseline_preserves_reasons(self, tmp_path):
        f1 = linter.Finding(path="a.py", line=1, col=0, rule="APX201",
                            severity="error", message="m", symbol="s1")
        f2 = linter.Finding(path="b.py", line=2, col=0, rule="APX202",
                            severity="error", message="m", symbol="s2")
        base = tmp_path / "base.txt"
        base.write_text(f"{f1.key}  # curated human reason\n")
        linter.write_baseline([f1, f2], "base.txt",
                              repo_root=str(tmp_path))
        loaded = linter.load_baseline("base.txt", repo_root=str(tmp_path))
        assert loaded[f1.key] == "curated human reason"
        assert loaded[f2.key] == "accepted pre-existing finding"


    def test_apx501_module_import_forms(self):
        fs = _lint("""
            from jax.experimental import shard_map
        """)
        assert _rules(fs) == ["APX501"]
        fs = _lint("""
            import jax.experimental.shard_map as sm
        """)
        assert _rules(fs) == ["APX501"]

    def test_float_flag_rejects_nonfinite(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_MONITOR_STALL_S", "nan")
        with pytest.raises(ValueError, match="finite"):
            flags_mod.flag_float("APEX_TPU_MONITOR_STALL_S")
        monkeypatch.setenv("APEX_TPU_MONITOR_STALL_S", "inf")
        with pytest.raises(ValueError, match="finite"):
            flags_mod.flag_float("APEX_TPU_MONITOR_STALL_S")

    def test_flags_import_stays_light(self):
        """Importing the registry (what ops modules do at module scope)
        must not drag the linter/sanitizer machinery along."""
        import subprocess as sp

        code = (
            "import sys; import apex_tpu.analysis.flags; "
            "mods=[m for m in sys.modules "
            "if m.startswith('apex_tpu.analysis')]; "
            "assert 'apex_tpu.analysis.linter' not in mods, mods; "
            "assert 'apex_tpu.analysis.sanitizer' not in mods, mods; "
            "print('light')")
        out = sp.run([sys.executable, "-c", code], capture_output=True,
                     text=True,
                     cwd=os.path.dirname(os.path.dirname(
                         os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr
