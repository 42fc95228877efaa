"""The ``openpangu-ultra-moe.serve-reason-sat`` cell on the CPU: its code
path end to end at tiny sizes through the harness (kernels interpreted),
traced and untraced, its three roofline count functions against hand
counts, and the engine's sums that they read.

Nothing here is a measurement: a number from these runs is never a
device metric.  The tiny model keeps the published structure (a dense
layer, then four MoE layers that hold a share of their experts; latent
attention with its two ranks, position-free and rotary head dims and a V
width unequal to the QK width; sandwich norms) at widths a CPU can run.
"""
import json
import re

import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import serve_open_loop_routed as routed
from benchmarks.rooflines import latent_decode, mla_prefill, \
    moe_experts_held

CELL = "openpangu-ultra-moe.serve-reason-sat"
BENCH = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")

TINY_CONFIG = {
    "vocab_size": 160, "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 12,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "num_experts_per_tok": 2,
    "deployment_share": {"router_outputs": 16, "expert_first": 4,
                         "experts_held": 4}}
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


def tiny_job(**engine):
    _, config, traffic = bench_run.find_cell(BENCH, CELL)
    config.update(TINY_CONFIG)
    traffic["engine"].update(TINY_TRAFFIC["engine"], **engine)
    return bench_run.resolve(config["builder"])(config, traffic, 5)


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
    assert set(line["metrics"]) == {"page_fill_pct.sat",
                                    "experts_hit_pct.reason"}
    grid = json.loads(re.search(r"decode_grid=(\{.*?\})", out).group(1))
    assert 0 < grid["ticks"] <= grid["rows"] <= grid["grid_rows"]
    assert line["metrics"]["page_fill_pct.sat"]["value"] == pytest.approx(
        100 * grid["live_pages"] / grid["grid_pages"])
    assert 0 < line["metrics"]["experts_hit_pct.reason"]["value"] <= 100


def test_the_cell_is_declared_as_the_issue_names_it():
    cell, config, traffic = bench_run.find_cell(BENCH, CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "serve_open_loop_routed"
    assert declared("end_to_end") == {"serve_tokens_per_s", "setup_s"}
    assert declared("per_layer") == {
        "decode_tick_ms.sat", "decode_kernel_ms.sat", "device_idle_pct.sat",
        "engine_step_host_ms.sat", "page_fill_pct.sat", "moe_ms.code",
        "moe_route_ms.code", "attn_mla_ms.reason",
        "latent_decode_roofline.reason", "prefill_2048_ms.reason",
        "mla_prefill_roofline.reason", "moe_hbm_roofline.reason",
        "experts_hit_pct.reason"}
    eng = traffic["engine"]
    assert (eng["block_size"], eng["num_blocks"]) == (16, 24577)
    assert eng["page_rungs"] == [64, 128, 288]
    assert eng["batch_rungs"] == [16, 32, 64] and eng["speculate_k"] == 0
    assert traffic["max_total_tokens"] == 4608 == 288 * 16
    assert traffic["rate_per_s"] == pytest.approx(
        traffic["knee_per_s"] * traffic["share_of_knee"])
    assert 1.25 <= traffic["share_of_knee"] <= 1.5
    # no width differs from the source; the cuts are the four named
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    share = config["deployment_share"]
    assert share["router_outputs"] == config["published"][
        "n_routed_experts"] == 16 * config["n_routed_experts"]
    assert config["vocab_size"] * share["vocab_shards"] \
        == config["published"]["vocab_size"]


def test_the_engine_fills_the_rooflines_fact_while_a_profile_records():
    """The rooflines read the engine's own dict, the model's shapes put
    beside its sums by the builder; the engine adds to it only while a
    profiler session (or a tracer) records."""
    from apex_tpu.monitor import tracing

    job = tiny_job()
    sums = job.facts["tick_sums"]
    assert sums is job.engine.tick_sums
    assert sums["latent_dim"] == 40 and sums["value_dim"] == 32
    assert "rows" not in sums and "latent_tokens" not in sums
    for i, n in enumerate((30, 11)):
        job.engine.submit(job.make_request(f"r{i}", list(range(1, n)), 6))
    job.engine.step()
    assert "rows" not in sums          # a tick ran and nothing recorded
    tracing.set_tracer(tracing.SpanTracer())
    try:
        job.engine.step()
    finally:
        tracing.set_tracer(None)
    # the second tick: sequences of 31 and 12 positions, pages of 4, five
    # latent layers; four MoE layers that hold 4 experts of 16
    assert sums["ticks"] == 1 and sums["rows"] == 2
    assert sums["latent_tokens"] == 5 * (31 + 12)
    assert sums["latent_pages"] == 5 * (8 + 3)
    assert sums["experts_slots"] == 4 * 4
    assert sums["experts_hit"] <= sums["pairs_held"] <= 2 * 2 * 4
    flops, nbytes = latent_decode.ticks(**sums)
    assert flops == 2 * 4 * (40 + 32) * 5 * 43
    flops, _ = moe_experts_held.ticks(**sums)
    assert flops == 6 * 32 * 64 * sums["pairs_held"]
    assert "mtp_proposed" not in sums


def test_the_engine_counts_the_mtp_drafts_while_a_profile_records():
    from apex_tpu.monitor import tracing

    job = tiny_job(speculate_k=1)
    engine, sums = job.engine, job.facts["tick_sums"]
    assert engine.cache_cfg.num_layers == 6
    engine.submit(job.make_request("r0", list(range(1, 9)), 8))
    engine.step()
    assert "mtp_proposed" not in sums
    tracing.set_tracer(tracing.SpanTracer())
    try:
        engine.step()
    finally:
        tracing.set_tracer(None)
    assert sums["mtp_proposed"] == 1 and sums["mtp_accepted"] in (0, 1)


def test_latent_decode_counts_at_one_small_shape():
    """Two ticks of 3 and 2 live rows through 2 latent layers; 70 live
    cached positions summed over rows, layers and ticks; 4 heads, a
    latent of 10 values of which 8 are the value, bf16: a cached token
    is read once (10 x 2 bytes) and costs 2 x 4 x (10 + 8) flops; each
    row's queries (4 x 10) are read and its output (4 x 8) written, a
    layer."""
    flops, nbytes = latent_decode.ticks(
        latent_tokens=70, rows=5, layers=2, heads=4, latent_dim=10,
        value_dim=8, latent_pages=30, experts_hit=3)    # others ignored
    assert flops == 2 * 4 * 18 * 70
    assert nbytes == 70 * 10 * 2 + 5 * 2 * 4 * 18 * 2


def test_mla_prefill_counts_at_one_small_shape():
    """One prefill of 6 positions, 3 layers of 2 heads that score on 5
    dims and carry 4: 21 (query, key) pairs a head, 2 x (5 + 4) flops a
    pair; q and k (5 wide), v and o (4 wide) once each, bf16."""
    flops, nbytes = mla_prefill.rung(seq=6, heads=2, qk_dim=5, v_dim=4,
                                     layers=3, rows=9)  # others ignored
    assert flops == 3 * 2 * 21 * 2 * 9
    assert nbytes == 3 * 2 * 6 * 2 * 9 * 2
    # the metric's own rung is the default
    assert mla_prefill.rung(heads=1, qk_dim=1, v_dim=1, layers=1)[0] \
        == 2048 * 2049 // 2 * 4


def test_held_expert_counts_at_one_small_shape():
    """Ticks in which 9 routed pairs fell on this chip's experts, 4
    distinct held experts hit, experts of width 4 on hidden 8: each pair
    6 x 4 x 8 flops, its input (bf16) and output (float32) once; each
    expert hit 3 x 8 x 4 bf16 weights.  ``rows`` plays no part: the
    pairs routed elsewhere are not this chip's."""
    flops, nbytes = moe_experts_held.ticks(
        experts_hit=4, pairs_held=9, hidden=8, expert_width=4, rows=50,
        experts_slots=16)                               # others ignored
    assert flops == 9 * 6 * 4 * 8
    assert nbytes == 4 * 3 * 8 * 4 * 2 + 9 * 8 * (2 + 4)


# --- the kind's limits on this model ----------------------------------------
# (largest margin, mean margin, share over LOGIT_MARGIN) on the chip
# (PERF.md section 6, PR 34): the largest each that the change gave over
# twenty-seven runs of the cell, and ``benchmarks/control_openpangu.py``'s two
# controls on the served path
CHANGE = (0.95964, 0.003479, 0.0104)
FLOAT8 = (1.31146, 0.079636, 0.2345)      # weights rounded to float8_e4m3
FOREIGN = (12.3421, 0.013215, 0.0031)     # one emitted token replaced


def test_the_routed_kinds_limits_stand_between_this_models_readings():
    """The accepted kind's three limits were found on another model;
    they hold here too: the change inside all three, the float8 control
    refused by the mean and by the share (not by the per-token limit),
    the planted token by the per-token limit alone."""
    limits = (routed.TOKEN_MARGIN, routed.MEAN_MARGIN, routed.OVER_SHARE)
    assert all(c < limit for c, limit in zip(CHANGE, limits))
    assert [f > limit for f, limit in zip(FLOAT8, limits)] \
        == [False, True, True]
    assert [f > limit for f, limit in zip(FOREIGN, limits)] \
        == [True, False, False]
    # room on the change's side: the mean and the share by a factor of
    # fourteen and more, the per-token limit by nearly two
    assert CHANGE[1] * 14 < routed.MEAN_MARGIN
    assert CHANGE[2] * 14 < routed.OVER_SHARE
    assert CHANGE[0] * 1.8 < routed.TOKEN_MARGIN
