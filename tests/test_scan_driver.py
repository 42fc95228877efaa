"""Batched-step scan driver + AOT/persistent-compile-cache (ISSUE-8).

The dispatch-amortization contract: K train steps per jit call must be
a pure packaging change — bitwise-identical state evolution to the
per-step loop (including an overflow-skip step landing mid-window),
the full per-step metric series drained ceil(N/K) times, resilience
boundaries on K-step edges (a kill mid-window resumes from the last
K-boundary checkpoint), and a second process warm-starting its
compiles from the persistent cache.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.monitor import MemorySink
from apex_tpu.testing.standalone_gpt import (build_train_step_scan,
                                             make_smoke_setup,
                                             train_smoke,
                                             wrap_scan_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trees_bitwise_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        return False
    for x, y in zip(la, lb):
        if hasattr(x, "dtype") or hasattr(y, "dtype"):
            if not (np.asarray(x) == np.asarray(y)).all():
                return False
        elif x != y:
            return False
    return True


def _assert_trees_close_fp16(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            atol=2e-3, rtol=1e-2)


def _loss_series(sink):
    return [(e.step, e.value) for e in sink.events
            if e.kind == "metric" and e.name == "loss"]


def _drain_events(sink):
    return [e for e in sink.events
            if e.kind == "telemetry" and e.name == "telemetry_drain"]


class TestScanBitwise:
    def test_k1_vs_k4_vs_classic_bitwise(self):
        """K is a packaging choice, not a numerics choice: the scan
        driver at K=1 and the classic per-step loop land on
        bitwise-identical params/masters/scaler after 8 steps, and the
        drained loss series is the same step-for-step.  K=4 is the
        same program — K=2, 4 and 8 agree bitwise with each other, and
        with XLA:CPU's while-loop simplifier disabled
        (``--xla_disable_hlo_passes=while-loop-simplifier``) all of
        K=1, K=4 and classic do — but as compiled on the CPU backend a
        real loop's body is fused differently from the straight-line
        step and moves <1% of the fp16 params by one ulp, so that one
        comparison holds to fp16 tolerance (as for BERT below)."""
        runs = {}
        for label, kw in (("k1", dict(scan_steps=1)),
                          ("k4", dict(scan_steps=4)),
                          ("classic", {})):
            sink = MemorySink()
            loss, params, state, done = train_smoke(
                steps=8, sink=sink, return_state=True, **kw)
            assert done == 8
            runs[label] = (loss, params, state, sink)
        assert _trees_bitwise_equal(runs["k1"][1], runs["classic"][1]), \
            "params diverged: k1 vs classic"
        assert _trees_bitwise_equal(runs["k1"][2], runs["classic"][2]), \
            "amp state diverged: k1 vs classic"
        for which in (1, 2):
            _assert_trees_close_fp16(runs["k1"][which], runs["k4"][which])
        # same per-step loss series, reconstructed from the ring
        s1 = _loss_series(runs["k1"][3])
        s4 = _loss_series(runs["k4"][3])
        assert [s for s, _ in s1] == [s for s, _ in s4] == list(range(8))
        for (_, a), (_, b) in zip(s1, s4):
            assert abs(a - b) < 1e-2
        # drain cadence: ceil(8/1)=8 vs ceil(8/4)=2
        assert len(_drain_events(runs["k1"][3])) == 8
        assert len(_drain_events(runs["k4"][3])) == 2

    def test_overflow_skip_inside_window_bitwise(self):
        """An overflow step landing INSIDE a scan window skips its
        update and backs the scaler off exactly as the per-step loop
        would: fp16 params at the O2 init scale 2^16 overflow the
        scaled grads on the first steps (2*scale > fp16 max), so
        window [0,4) of the K=4 run contains genuine skip steps —
        state must still be bitwise-equal to K=1."""
        from apex_tpu import amp
        from apex_tpu.optimizers import fused_sgd

        def make():
            amp_opt = amp.AmpOptimizer(fused_sgd(0.1),
                                       amp.get_policy("O2"),
                                       check_finite=True)
            params = {"w": jnp.full((4, 128), 1.0, jnp.float16)}
            state = amp_opt.init(params)

            def step_fn(p, s):
                def loss_fn(pp):
                    loss = jnp.sum(pp["w"].astype(jnp.float32) ** 2)
                    return amp_opt.scale_loss(loss, s), loss

                grads, loss = jax.grad(loss_fn, has_aux=True)(p)
                new_p, new_s, info = amp_opt.apply_gradients(grads, s, p)
                gnorm = info.grad_norm if info.grad_norm is not None \
                    else jnp.float32(0.0)
                return new_p, new_s, loss, gnorm, info

            return step_fn, params, state

        results = {}
        for k in (1, 4):
            step_fn, params, state = make()
            scan = wrap_scan_step(step_fn, k)
            params, state = jax.tree_util.tree_map(jnp.array,
                                                   (params, state))
            skipped = []
            for _ in range(8 // k):
                params, state, loss, gnorm, info = scan(params, state)
                skipped.append(int(info.steps_skipped))
            results[k] = (params, state, skipped)
        p1, s1, sk1 = results[1]
        p4, s4, sk4 = results[4]
        assert _trees_bitwise_equal(p1, p4)
        assert _trees_bitwise_equal(s1, s4)
        # the skips genuinely happened, inside the K=4 run's first
        # window (scale 2^16 and 2^15 both overflow 2*w*scale in fp16)
        assert sk4[0] >= 2, sk4
        assert float(s4.scaler.loss_scale) < 65536.0

    def test_scan_validations(self):
        def step_fn(p, s):
            raise AssertionError("never traced")

        with pytest.raises(ValueError, match=">= 1 step"):
            wrap_scan_step(step_fn, 0)
        from apex_tpu.monitor.tracing import DeviceMetricsBuffer

        with pytest.raises(ValueError, match="capacity"):
            wrap_scan_step(step_fn, 4,
                           telemetry=DeviceMetricsBuffer(capacity=2))
        with pytest.raises(ValueError, match="conflicts"):
            train_smoke(steps=4, scan_steps=2, drain_every=3)


class TestScanLoop:
    def test_partial_window_drains_and_waterfall(self, tmp_path):
        """7 steps at K=3 run as windows of 3+3+1 (the remainder
        window is its own AOT compile): all 7 losses drain in
        ceil(7/3)=3 drains, and the trace carries one waterfall row
        per window with scan_k stamped (tools/trace_check.py's scan
        assertion)."""
        from apex_tpu.monitor.tracing import check_trace

        jsonl = str(tmp_path / "scan.jsonl")
        loss, params, state, done = train_smoke(
            steps=7, scan_steps=3, jsonl=jsonl,
            trace_dir=str(tmp_path), return_state=True)
        assert done == 7
        events = [json.loads(l) for l in open(jsonl)]
        losses = [e for e in events
                  if e["kind"] == "metric" and e["name"] == "loss"]
        assert [e["step"] for e in losses] == list(range(7))
        drains = [e for e in events
                  if e["kind"] == "telemetry"
                  and e["name"] == "telemetry_drain"]
        assert len(drains) == 3
        assert check_trace(jsonl, scan_k=3, steps=7) == []
        # wrong expectations must fail loudly
        assert check_trace(jsonl, scan_k=2, steps=7) != []
        assert check_trace(jsonl, scan_k=3, steps=9) != []
        # per-window AOT compile events for both lengths (3 and 1)
        compiles = [e for e in events if e["name"] == "aot_compile"]
        assert sorted(e["attrs"]["scan_k"] for e in compiles) == [1, 3]

    def test_env_flag_enables_scan(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_SCAN_STEPS", "2")
        sink = MemorySink()
        loss, params, state, done = train_smoke(steps=4, sink=sink,
                                                return_state=True)
        assert done == 4
        start = [e for e in sink.events if e.name == "run_start"][0]
        assert start.attrs["scan_steps"] == 2
        assert len(_drain_events(sink)) == 2

    def test_bert_scan_driver_shared_wrapper(self):
        """The BERT driver rides the same wrap_scan_step.  K=1 is
        bitwise vs the classic loop; K=4 is allclose-at-fp16 only —
        XLA unrolls/fuses a 4-trip scan body differently than a
        1-trip one on this path (masked softmax + layernorm), moving
        3 leaves by ~1 fp16 ulp; the GPT driver shows the same — see
        TestScanBitwise."""
        from apex_tpu.testing import standalone_bert

        sink0, sink1, sink4 = MemorySink(), MemorySink(), MemorySink()
        _, p0, s0, d0 = standalone_bert.train_smoke(
            steps=4, sink=sink0, return_state=True)
        _, p1, s1, d1 = standalone_bert.train_smoke(
            steps=4, scan_steps=1, sink=sink1, return_state=True)
        _, p4, s4, d4 = standalone_bert.train_smoke(
            steps=4, scan_steps=4, sink=sink4, return_state=True)
        assert d0 == d1 == d4 == 4
        assert _trees_bitwise_equal(p0, p1)
        assert _trees_bitwise_equal(s0, s1)
        _assert_trees_close_fp16(p1, p4)
        l1, l4 = _loss_series(sink1), _loss_series(sink4)
        assert [s for s, _ in l1] == [s for s, _ in l4] == list(range(4))
        for (_, a), (_, b) in zip(l1, l4):
            assert abs(a - b) < 1e-2


class TestScanResilience:
    def test_kill_mid_window_resumes_from_k_boundary(self, tmp_path):
        """A crash during window 2 (steps 4..7) loses that window's
        progress; the resume lands on the checkpoint at step 4 — the
        last K-boundary — and the completed run is bitwise-equal to an
        uninterrupted one."""
        from apex_tpu.resilience import InjectedCrash

        ck = str(tmp_path / "ck")
        with pytest.raises(InjectedCrash):
            train_smoke(steps=8, scan_steps=4, ckpt_dir=ck,
                        ckpt_every=4, fault="crash@4",
                        sink=MemorySink(), return_state=True)
        sink = MemorySink()
        _, params, state, done = train_smoke(
            steps=8, scan_steps=4, ckpt_dir=ck, ckpt_every=4,
            sink=sink, return_state=True)
        assert done == 8
        resumed = [e for e in sink.events if e.name == "run_resumed"]
        assert len(resumed) == 1 and resumed[0].value == 4
        _, p_clean, s_clean, _ = train_smoke(
            steps=8, scan_steps=4, sink=MemorySink(),
            return_state=True)
        assert _trees_bitwise_equal(params, p_clean)
        assert _trees_bitwise_equal(state, s_clean)

    def test_ckpt_cadence_not_multiple_of_k(self, tmp_path):
        """A checkpoint cadence that is not a multiple of K must not
        alias to silence: done only ever equals window edges, so a
        plain ``done % ckpt_every`` check would save at lcm(K,
        ckpt_every) intervals (here: never).  The crossing check saves
        at the first edge at or past each cadence point instead —
        K=4, ckpt_every=3, 10 steps -> checkpoints at 4, 8, 10."""
        ck = str(tmp_path / "ck")
        _, _, _, done = train_smoke(
            steps=10, scan_steps=4, ckpt_dir=ck, ckpt_every=3,
            sink=MemorySink(), return_state=True)
        assert done == 10
        on_disk = sorted(int(d) for d in os.listdir(ck) if d.isdigit())
        assert on_disk == [4, 8, 10]

    def test_misaligned_fault_fires_at_window_edge(self, tmp_path):
        """A fault aimed INSIDE a window (crash@5 at K=3: window
        [3, 6)) must not silently no-op just because step 5 is never a
        window start: it fires at the window's start edge — the only
        host boundary that exists under the scan driver — and the
        resumed run completes bitwise-equal to an uninterrupted one."""
        from apex_tpu.resilience import InjectedCrash

        ck = str(tmp_path / "ck")
        with pytest.raises(InjectedCrash):
            train_smoke(steps=9, scan_steps=3, ckpt_dir=ck,
                        ckpt_every=3, fault="crash@5",
                        sink=MemorySink(), return_state=True)
        sink = MemorySink()
        _, params, state, done = train_smoke(
            steps=9, scan_steps=3, ckpt_dir=ck, ckpt_every=3,
            sink=sink, return_state=True)
        assert done == 9
        resumed = [e for e in sink.events if e.name == "run_resumed"]
        assert len(resumed) == 1 and resumed[0].value == 3
        _, p_clean, s_clean, _ = train_smoke(
            steps=9, scan_steps=3, sink=MemorySink(),
            return_state=True)
        assert _trees_bitwise_equal(params, p_clean)
        assert _trees_bitwise_equal(state, s_clean)

    def test_sigterm_between_windows_clean_exit(self, tmp_path):
        """A termination request raised mid-run is honored at the next
        window edge: final synchronous checkpoint + CLEAN_EXIT marker,
        steps_done on a K boundary."""
        ck = str(tmp_path / "ck")
        sink = MemorySink()
        _, _, _, done = train_smoke(
            steps=8, scan_steps=2, ckpt_dir=ck, ckpt_every=2,
            fault="sigterm@4", sink=sink, return_state=True)
        assert done in (4, 6) and done % 2 == 0
        assert os.path.exists(os.path.join(ck, "CLEAN_EXIT.json"))
        assert any(e.name == "preempt_exit" for e in sink.events)


class TestAotCompileCache:
    def test_aot_warmup_unknown_entry_raises(self):
        from apex_tpu.testing.entry_points import aot_warmup

        with pytest.raises(KeyError, match="no_such_entry"):
            aot_warmup(["no_such_entry"])

    def test_second_process_hits_persistent_cache(self, tmp_path):
        """The zero→warm proof: process 1 populates the persistent
        cache via the AOT registry warmup; process 2, same cache dir,
        must serve its compiles from it (--expect-cache-hits exits 0
        only if jax reported persistent-cache hits)."""
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   APEX_TPU_COMPILE_CACHE_DIR=str(tmp_path / "cc"))
        cmd = [sys.executable, "-m", "apex_tpu.testing.entry_points",
               "--aot", "--entry", "fused_pipeline_step"]
        r1 = subprocess.run(cmd, cwd=REPO, env=env,
                            capture_output=True, text=True, timeout=300)
        assert r1.returncode == 0, r1.stderr
        assert "fused_pipeline_step" in r1.stdout
        r2 = subprocess.run(cmd + ["--expect-cache-hits"], cwd=REPO,
                            env=env, capture_output=True, text=True,
                            timeout=300)
        assert r2.returncode == 0, (r2.stdout, r2.stderr)
        assert "persistent-cache hit" in r2.stdout

    def test_configure_compile_cache_noop_without_flag(self, monkeypatch):
        from apex_tpu.utils import compile_cache

        monkeypatch.delenv("APEX_TPU_COMPILE_CACHE_DIR", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(compile_cache, "_configured", None)
        assert compile_cache.configure_compile_cache() is None


class TestScanEntryAudit:
    def test_entry_registered(self):
        from apex_tpu.testing.entry_points import ENTRY_POINTS

        ep = ENTRY_POINTS["gpt_train_step_scan"]
        assert ep.dead_args == (0, 1, 2)
        assert ep.policy == "O2"

    def test_scan_entry_audit_clean_and_donated(self):
        """The audited form of the tentpole's donation claim: the scan
        entry lowers with params/amp state/telemetry ring ALL donated
        (APX601 clean) and zero compiled-in host transfers (APX604);
        the committed baseline row exists."""
        from apex_tpu.analysis.hlo import (audit_entry_points,
                                           load_hlo_baseline)

        audits = audit_entry_points(REPO,
                                    names=["gpt_train_step_scan"])
        audit = audits["gpt_train_step_scan"]
        assert audit.findings == [], [f.render() for f in audit.findings]
        assert len(audit.donated) > 10  # the whole carry, not a token
        base = load_hlo_baseline(repo_root=REPO)
        assert "gpt_train_step_scan" in base["entries"]


class TestWaterfallScanExtras:
    def test_end_step_extra_fields(self):
        from apex_tpu.monitor.tracing import StepWaterfall

        t = [0.0]

        def clock():
            return t[0]

        wf = StepWaterfall(clock=clock)
        wf.begin_step(0)
        with wf.part("dispatch"):
            t[0] += 0.010
        row = wf.end_step(step=3, scan_k=4)
        assert row["scan_k"] == 4 and row["step"] == 3
        wf.begin_step(1)
        with pytest.raises(ValueError, match="_ms"):
            wf.end_step(step=1, bogus_ms=1.0)
