"""The ``laguna-xs2.serve-code-sat`` cell on the CPU: its code path end
to end at tiny sizes through the harness (kernels interpreted), traced
and untraced, and its two roofline count functions against hand counts.

Nothing here is a measurement: a number from these runs is never a
device metric.  The tiny model keeps the published structure (five
layers in the published order, grouped heads that differ by layer kind,
a window, rotary with YaRN, a dropless top-k mixture beside a shared
expert) at widths a CPU can run.
"""
import json
import re
import types

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import serve_open_loop_routed as routed
from benchmarks.rooflines import moe_experts, paged_decode_window

CELL = "laguna-xs2.serve-code-sat"
BENCH = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")

TINY_CONFIG = {
    "vocab_size": 300, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "sliding_window": 8,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4]}
TINY_TRAFFIC = {
    "rate_per_s": 6.0, "lead_in_s": 0.5, "trace_seconds": 0.5,
    "prompt_tokens": {"median": 12, "min": 4, "max": 40},
    "output_tokens": {"median": 6, "min": 2, "max": 12},
    "max_total_tokens": 64,
    "engine": {"block_size": 4, "page_rungs": [8, 16],
               "batch_rungs": [4], "num_blocks": 65}}
TINY = {"config": TINY_CONFIG, "traffic": TINY_TRAFFIC}


def declared(group):
    return {m["name"] for m in bench_run.metrics_of(BENCH, group, CELL)}


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end_at_tiny_sizes(trace, capsys):
    line = bench_run.run_cell(CELL, 3000000019, 1.0, trace,
                              overrides=TINY, require_tpu=False)
    out = capsys.readouterr().out
    assert "compiles_in_window=0" in out
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 6
    if not trace:
        assert set(line["metrics"]) == declared("end_to_end") \
            == {"serve_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
        return
    # no device plane on the CPU: the readers of device time find
    # nothing and no CPU number takes a device metric's name; the
    # counters are the engine's own and a CPU run gives them rightly
    assert set(line["metrics"]) == {"page_fill_pct.sat"}
    grid = json.loads(re.search(r"decode_grid=(\{.*?\})", out).group(1))
    assert 0 < grid["ticks"] <= grid["rows"] <= grid["grid_rows"]
    assert line["metrics"]["page_fill_pct.sat"]["value"] == pytest.approx(
        100 * grid["live_pages"] / grid["grid_pages"])


def test_the_engine_fills_the_rooflines_fact_while_a_profile_records():
    """The rooflines read the engine's own dict, the model's shapes
    put beside its sums by the builder; the engine adds to it only
    while a profiler session (or a tracer) records, and the counts
    agree with one another."""
    from apex_tpu.monitor import tracing

    _, config, traffic = bench_run.find_cell(BENCH, CELL)
    config.update(TINY_CONFIG)
    traffic["engine"].update(TINY_TRAFFIC["engine"])
    job = bench_run.resolve(config["builder"])(config, traffic, 5)
    sums = job.facts["tick_sums"]
    assert sums is job.engine.tick_sums
    assert sums["moe_layers"] == 4 and "rows" not in sums
    for i, n in enumerate((30, 11)):
        job.engine.submit(job.make_request(f"r{i}", list(range(1, n)), 6))
    job.engine.step()
    assert "rows" not in sums          # a tick ran and nothing recorded
    tracing.set_tracer(tracing.SpanTracer())
    try:
        job.engine.step()
    finally:
        tracing.set_tracer(None)
    # the second tick: sequences of 31 and 12 positions, pages of 4,
    # window 8 on three layers, full on two
    assert sums["ticks"] == 1 and sums["rows"] == 2
    assert sums["pages_full"] == 2 * (8 + 3)
    assert sums["tokens_full"] == 2 * (31 + 12)
    assert sums["pages_dead"] == 3 * ((31 - 8) // 4 + (12 - 8) // 4)
    assert sums["pages_window"] == 3 * (8 + 3) - sums["pages_dead"]
    assert sums["tokens_window"] == 3 * (8 + 8)
    assert 2 * 4 <= sums["experts_hit"] <= 4 * 4      # 2 rows x top-2
    assert 4 <= sums["expert_max_rows"] <= 2 * 4
    flops, nbytes = moe_experts.ticks(**sums)
    assert flops == 6 * 32 * 64 * (2 * 4 * 2)


def test_moe_expert_counts_at_one_small_shape():
    """Two ticks of 3 and 2 live rows through 2 MoE layers, top-2 of 8
    experts of width 4 on hidden 8: 10 rows x 2 = 20 routed tokens,
    each 6 x 4 x 8 flops; the distinct experts hit (7 over both layers
    and ticks) are read, 3 x 8 x 4 bf16 weights each, plus each row's
    input (bf16) and output (float32) a layer."""
    flops, nbytes = moe_experts.ticks(
        experts_hit=7, rows=5, moe_layers=2, hidden=8, expert_width=4,
        experts_per_token=2, pages_full=99)           # others ignored
    assert flops == 20 * 6 * 4 * 8
    assert nbytes == 7 * 3 * 8 * 4 * 2 + 5 * 2 * 8 * (2 + 4)


def test_paged_decode_window_counts_at_one_small_shape():
    """One tick, one row of 21 positions, pages of 4 (6 pages), window
    8: one full layer of 4 query heads reads 6 pages and 21 positions,
    two windowed layers of 6 query heads read pages 3..5 (positions
    13..20 lie in pages 3, 4, 5) and 8 positions each; 2 cache heads
    of size 16, bf16."""
    flops, nbytes = paged_decode_window.ticks(
        pages_full=6, tokens_full=21, pages_window=2 * 3,
        tokens_window=2 * 8, rows=1, full_layers=1, window_layers=2,
        full_heads=4, window_heads=6, kv_heads=2, head_dim=16,
        block_size=4, experts_hit=5)                  # others ignored
    page = 4 * 2 * 16 * 2
    assert nbytes == 2 * (6 + 6) * page + 2 * 1 * (4 + 2 * 6) * 16 * 2
    assert flops == 4 * 16 * (21 * 4 + 16 * 6)


# --- the kind's limits ------------------------------------------------------

class _Sample:
    """A job and tracks whose reference margins are given: what
    ``reference_check`` needs of a run, and nothing of a model."""

    def __init__(self, margins):
        self.rows = [np.asarray(m, np.float32) for m in margins]
        self.tracks = [types.SimpleNamespace(
            in_window=True, request=types.SimpleNamespace(
                terminal="finished", prompt=[1, 2, 3],
                out_tokens=[0] * len(m))) for m in self.rows]
        self.calls = 0

    def reference_margins(self, tokens, emitted):
        # the kind asks in the order of its picks, which the seed fixes;
        # every row is as good as another here, so hand them out in turn
        m = self.rows[self.calls % len(self.rows)]
        self.calls += 1
        out = np.zeros((1, tokens.shape[1]), np.float32)
        out[0, 2:2 + m.size] = m         # from the prompt's last position
        return out, np.full_like(out, 0.9)


def _margins(rng, tokens, *, mean, over_share, worst):
    """``tokens`` margins with that share over ``LOGIT_MARGIN``, that
    mean and that largest value: the rest are the reference's arg-max
    (0), as nine in ten of the chip's are."""
    m = np.zeros(tokens)
    over = rng.choice(tokens, int(round(over_share * tokens)), replace=False)
    m[over] = routed.LOGIT_MARGIN * 1.01
    m[over[0]] = worst
    m[over[1:]] += (mean * tokens - m.sum()) / (over.size - 1)
    assert m.max() == worst and abs(m.mean() - mean) < 1e-9
    return m


# (mean, share over 0.05, largest) as read on the chip (PERF.md section
# 6, PR 29): the largest of each the change gave over its runs, and the
# smallest of each over the engine serving float8_e4m3-rounded weights
# (``benchmarks/control_routed.py``) and three such forwards without
# the cache
CHANGE_WORST = dict(mean=0.0216, over_share=0.098, worst=0.90)
FLOAT8_BEST = dict(mean=0.159, over_share=0.470, worst=1.09)


@pytest.mark.parametrize("name, reading, refused_by", [
    ("the change's largest readings", CHANGE_WORST, set()),
    ("float8 weights, their smallest readings", FLOAT8_BEST,
     {"MEAN_MARGIN", "OVER_SHARE"}),
    ("one foreign token in the change's sample",
     dict(CHANGE_WORST, worst=3.0), {"TOKEN_MARGIN"}),
    ("float8 in the mean alone",
     dict(mean=0.159, over_share=0.19, worst=1.5), {"MEAN_MARGIN"}),
    ("float8 in the share alone", dict(FLOAT8_BEST, mean=0.049,
                                       worst=0.11), {"OVER_SHARE"}),
])
def test_the_routed_kinds_limits_stand_between_the_readings(
        name, reading, refused_by):
    """Each limit of ``serve_open_loop_routed`` lies between what the
    change reads and what its control reads, so a sample with the
    change's worst readings is ``correct`` and one with a control's
    best is refused, by the limit named and by no other.  An edit that
    moves a limit past either reading fails here."""
    rng = np.random.default_rng(7)
    sample = _Sample([_margins(rng, 100, **reading) for _ in range(4)])
    faults = []
    facts = routed.reference_check(
        sample, {"reference_sample": 4, "max_total_tokens": 128},
        sample.tracks, 11, faults)
    assert facts["reference_checked"] == "4req/400tok"
    assert facts["reference_mean_margin"] == pytest.approx(reading["mean"])
    named = {limit for limit in ("TOKEN_MARGIN", "MEAN_MARGIN", "OVER_SHARE")
             if any(limit in fault for fault in faults)}
    assert named == refused_by and len(faults) == len(refused_by), faults


def test_a_run_of_the_kind_is_held_by_its_own_check(monkeypatch):
    """``run`` stands the kind's check in for the accepted kind's for
    the length of a run, and puts it back."""
    from benchmarks.kinds import serve_open_loop as base

    seen = []
    monkeypatch.setattr(base, "run", lambda job, traffic, **kw: seen.append(
        base.reference_check) or "result")
    assert routed.run(None, None) == "result"
    assert seen == [routed.reference_check]
    assert base.reference_check is not routed.reference_check


def test_the_controls_run_through_the_kinds_check_at_tiny_sizes():
    """``benchmarks.control_routed`` end to end on the CPU: the float8
    engine, the cell as it is and the planted token each give a sample
    through ``reference_check``.  The limits are the published widths'
    (a tiny model's logits spread 0.16, not 0.9), so what is held here
    is that each control moves the number it is for; the readings that
    count are the chip's."""
    from benchmarks import control_routed

    _, config, traffic = bench_run.find_cell(BENCH, CELL)
    config.update(TINY_CONFIG)
    for key, value in TINY_TRAFFIC.items():
        traffic[key] = {**traffic[key], **value} \
            if isinstance(value, dict) else value
    job = bench_run.resolve(config["builder"])(config, traffic, 5)
    got = control_routed.controls(job, config, traffic, 5, 1.0)
    assert list(got) == ["float8", "change", "foreign"]
    assert got["change"]["correct"] and not got["change"]["faults"]
    assert got["float8"]["reference_mean_margin"] \
        > got["change"]["reference_mean_margin"] + 1e-3
    assert got["foreign"]["reference_max_margin"] \
        > got["change"]["reference_max_margin"] + 0.1
    assert got["foreign"]["reference_checked"] \
        == got["change"]["reference_checked"]
