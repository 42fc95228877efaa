"""chip_smoke.py off the chip: the phases run tiny on the CPU (kernels in
interpret mode), and ``main()`` never reports success without a TPU.

Plus the two places this PR stopped from hiding the device: the
compile-cache precedence and ``device_spec`` on an unknown accelerator.
"""
import json
import types

import jax
import pytest

import chip_smoke

# d=64 heads like GPT-2's, so the E-layout flash path is the one taken
TINY = dict(vocab=256, hidden=128, num_heads=2, num_layers=2)


def _stub_tpu(kind="TPU v5 lite", n=1):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind,
                                  memory_stats=lambda: {})
            for _ in range(n)]


class TestPhasesTinyOnCpu:
    """The phases as ``main()`` calls them, on jax's default device —
    here the CPU, where the kernels run interpreted and a compiled
    program holds no ``tpu_custom_call``."""

    SERVE = dict(max_seq=128, block_size=16, batch_rungs=(1, 2),
                 page_rungs=(2, 8), num_requests=3, max_new_tokens=4,
                 seed=5)

    def test_train_phase(self):
        facts = chip_smoke.train_phase(jax.devices()[0], **TINY, batch=2,
                                       seq=128, steps=6)
        assert len(facts["losses"]) == 6
        assert facts["losses"][-1] < facts["losses"][0]
        assert facts["kernels_in_step"] == 0
        assert facts["peak_bytes_in_use"] is None

    def test_serve_phase(self):
        facts = chip_smoke.serve_phase(jax.devices()[0], **TINY,
                                       **self.SERVE, prompt_span=(1, 124))
        assert facts["requests_done"] == 3
        assert facts["programs_compiled"] == 6
        assert facts["post_warmup_recompiles"] == 0
        assert facts["kernels_in_decode_step"] == 0
        assert facts["tokens_matching_reference_decode"].endswith("/12")

    def test_serve_phase_refuses_prompts_outside_the_span(self):
        with pytest.raises(RuntimeError, match="prompt lengths"):
            chip_smoke.serve_phase(jax.devices()[0], **TINY, **self.SERVE,
                                   prompt_span=(30, 124))

    def test_parity_phase(self):
        facts = chip_smoke.parity_phase(num_heads=2, head_dim=64, batch=4,
                                        seq=128, block_size=16, pages=4)
        assert facts["flash_decode_max_abs_err"] < 3e-2
        assert facts["flash_attention_e_max_abs_err"] < 3e-2

    def test_multichip_phase_on_four_virtual_devices(self):
        facts = chip_smoke.multichip_phase(
            jax.devices()[:4], **TINY, batch=8, seq=128, dp=2, tp=2,
            kernels_in_step=0)
        assert facts["mesh"] == {"pipe": 1, "data": 2, "tensor": 2}
        assert facts["losses"][1] < facts["losses"][0]
        # qkv, fc1 and the embedding are halved, the rest rides whole
        assert 0.5 < facts["share_of_adam_state_on_device_0"] < 1

    def test_multichip_phase_refuses_a_silent_change_of_kernels(self):
        with pytest.raises(RuntimeError, match="0 Mosaic kernels"):
            chip_smoke.multichip_phase(
                jax.devices()[:4], **TINY, batch=8, seq=128, dp=2, tp=2,
                kernels_in_step=3)

    def test_a_program_without_kernels_fails_on_a_tpu(self):
        # what the CPU compiles holds no Mosaic kernel: to a phase told
        # it runs on a TPU that is the silent fallback it must refuse
        with pytest.raises(RuntimeError, match="tpu_custom_call"):
            chip_smoke.train_phase(_stub_tpu()[0], **TINY, batch=2,
                                   seq=128, steps=1)


class TestTrainStepFeedsItself:
    """``build_gpt_3d``: a step's outputs are laid out as its inputs."""

    def test_second_call_adds_no_program_and_keeps_the_layout(self):
        import jax.numpy as jnp

        import __graft_entry__ as graft

        g = graft.build_gpt_3d(
            jax.devices()[:4], tp=2, pp=1, vocab=TINY["vocab"],
            hidden=TINY["hidden"], num_heads=TINY["num_heads"], seq=128,
            layers_per_stage=TINY["num_layers"], dtype=jnp.bfloat16)
        key = jax.random.PRNGKey(0)
        params, tokens, labels = g.place(g.init(key), *g.batch(key, 8))
        state = g.init_opt(params)

        def layout(tree):
            return [(x.sharding, x.ndim) for x in jax.tree.leaves(tree)]

        before = layout((params, state))
        assert any(not s.is_fully_replicated for s, _ in layout(state.m))
        for _ in range(3):
            params, state, _ = g.train_step(params, state, tokens, labels)
        assert g.train_step._cache_size() == 1
        assert all(a.is_equivalent_to(b, n) for (a, n), (b, _)
                   in zip(before, layout((params, state))))


class TestMain:
    @pytest.fixture
    def on_stub_tpu(self, monkeypatch):
        """``main()`` sees ``n`` stub TPU devices and sets no cache."""
        def install(n=1):
            monkeypatch.setattr(jax, "devices",
                                lambda *a: _stub_tpu(n=n))
            monkeypatch.setattr(
                "apex_tpu.utils.compile_cache.configure_compile_cache",
                lambda: None)
        return install

    def test_off_tpu_exits_nonzero_and_prints_no_result(self, capsys):
        assert chip_smoke.main([]) != 0
        assert chip_smoke.main(["--multichip"]) != 0
        out = capsys.readouterr()
        assert '"ok"' not in out.out
        assert "no TPU" in out.err

    def test_a_raising_phase_ends_the_run_without_a_result(
            self, monkeypatch, capsys, on_stub_tpu):
        on_stub_tpu()

        def boom(*a, **kw):
            raise RuntimeError("phase failed")

        monkeypatch.setattr(chip_smoke, "train_phase", boom)
        # nothing catches it: the interpreter exits non-zero
        with pytest.raises(RuntimeError, match="phase failed"):
            chip_smoke.main([])
        assert '"ok"' not in capsys.readouterr().out

    @pytest.mark.parametrize("argv,phases", [
        ([], ["train", "serve", "parity"]),
        (["--multichip"], ["multichip"]),
    ])
    def test_phases_run_and_last_line(self, monkeypatch, capsys,
                                      on_stub_tpu, argv, phases):
        n = 4 if argv else 1
        on_stub_tpu(n)
        ran = []
        for name in ("train", "serve", "parity", "multichip"):
            monkeypatch.setattr(
                chip_smoke, f"{name}_phase",
                lambda *a, _n=name, **kw: ran.append(_n))
        assert chip_smoke.main(argv) == 0
        assert ran == phases
        last = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(last) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": n}}

    def test_result_line_is_exactly_the_contract(self):
        assert chip_smoke.result_line(_stub_tpu()) == (
            '{"ok": true, "device": {"platform": "tpu", '
            '"kind": "TPU v5 lite", "count": 1}}')

    def test_unknown_chip_fails_the_device_phase(self):
        with pytest.raises(KeyError, match="no peak-rate row"):
            chip_smoke.device_phase(_stub_tpu(kind="TPU v9 mystery"))


class TestDeviceSpec:
    def test_cpu_row_only_for_the_cpu(self):
        from apex_tpu.pyprof.prof import device_spec

        assert device_spec().name == "host CPU"
        assert device_spec(_stub_tpu()[0]).name == "TPU v5e"
        with pytest.raises(KeyError):
            device_spec(types.SimpleNamespace(platform="gpu",
                                              device_kind="cpu-like H100"))

    def test_step_monitor_emits_no_mfu_for_an_unknown_accelerator(
            self, monkeypatch):
        from apex_tpu.monitor import MemorySink, StepMonitor

        monkeypatch.setattr(
            jax, "devices", lambda *a: _stub_tpu(kind="TPU v9 mystery"))
        sink = MemorySink()
        mon = StepMonitor(sink, tokens_per_step=8, flops_per_step=1e9)
        mon.start_step(0)
        mon.end_step(0, loss=1.0)
        names = {e.name for e in sink.events if e.kind == "metric"}
        assert "step_ms" in names and "mfu" not in names


class TestCompileCachePrecedence:
    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self, monkeypatch):
        from jax.experimental.compilation_cache import compilation_cache

        from apex_tpu.utils import compile_cache

        saved = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key")}
        monkeypatch.setattr(compile_cache, "_configured", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delenv("APEX_TPU_COMPILE_CACHE_DIR", raising=False)
        yield
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

    def test_jax_env_var_stands_and_no_directory_is_set(
            self, monkeypatch, tmp_path):
        from apex_tpu.utils import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setenv("APEX_TPU_COMPILE_CACHE_DIR", "/elsewhere")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_flag_next(self, monkeypatch, tmp_path):
        from apex_tpu.utils import compile_cache

        monkeypatch.setenv("APEX_TPU_COMPILE_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    def test_fixed_path_under_the_checkout_on_tpu(self, monkeypatch,
                                                  tmp_path):
        import os

        from apex_tpu.utils import compile_cache

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache._CHECKOUT_CACHE == os.path.join(
            repo, ".jax_cache")
        # same rule, pointed away from the real checkout for the test
        fixed = str(tmp_path / ".jax_cache")
        monkeypatch.setattr(compile_cache, "_CHECKOUT_CACHE", fixed)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert compile_cache.configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert os.path.isdir(fixed)

    def test_a_program_renamed_is_not_handed_the_old_names(
            self, monkeypatch, tmp_path):
        """jax's default cache key strips op names: a step that gained
        ``jax.named_scope``s would load the executable cached before it
        had them, and a device trace would show none (PR 27)."""
        import contextlib

        import jax.numpy as jnp

        from apex_tpu.utils import compile_cache

        monkeypatch.setenv("APEX_TPU_COMPILE_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)

        def build(scope):
            def step(x):
                with scope:
                    return jnp.sin(x) * 2 + 1
            return jax.jit(step)

        x = jnp.ones((64, 64))
        bare = build(contextlib.nullcontext())
        scoped = build(jax.named_scope("apex.optimizer"))
        assert bare.lower(x).as_text() == scoped.lower(x).as_text()
        bare.lower(x).compile()
        entries = len(list(tmp_path.iterdir()))
        assert entries > 0
        assert "apex.optimizer" in scoped.lower(x).compile().as_text()
        assert len(list(tmp_path.iterdir())) > entries

    def test_none_on_the_cpu(self):
        from apex_tpu.utils import compile_cache

        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
