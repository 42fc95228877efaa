"""The ``evabyte.serve-bytes-sat`` cell on the CPU: its code path end to
end at tiny sizes through the harness (kernels interpreted), traced and
untraced; its configuration against the published keys; its two
roofline count functions against hand counts; its controls through the
kind's own check.

Nothing here is a measurement: a number from these runs is never a
device metric.  The tiny model keeps the published structure (every
layer the same EVA block, the unit-offset norm, a head eight
vocabularies of 320 wide) at widths a CPU can run: window 32, chunk 4 =
pages of 4.
"""
import json
import re

import pytest

from benchmarks import run as bench_run
from benchmarks.kinds import serve_open_loop as kind
from benchmarks.rooflines import eva_decode, eva_prefill

CELL = "evabyte.serve-bytes-sat"
BENCH = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")

TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "window_size": 32, "chunk_size": 4, "init_std": 0.05}
TINY_TRAFFIC = {
    "rate_per_s": 6.0, "lead_in_s": 0.5, "trace_seconds": 0.5,
    "prompt_tokens": {"median": 40, "min": 8, "max": 100},
    "output_tokens": {"median": 10, "min": 4, "max": 20},
    "max_total_tokens": 128,
    "engine": {"block_size": 4, "prefill_chunk": 32,
               "chunk_rungs": [16, 32], "page_rungs": [16],
               "batch_rungs": [4], "num_blocks": 65}}
TINY = {"config": TINY_CONFIG, "traffic": TINY_TRAFFIC}

# config.json of the source, as the catalog beside the model-configs
# guide holds it (its `config`): every number under its own key
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32,
    "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}


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
    assert set(line["metrics"]) == {
        "eva_page_fill_pct.bytes", "eva_summary_rows_pct.bytes"}
    assert 0 < line["metrics"]["eva_page_fill_pct.bytes"]["value"] <= 100
    assert 0 < line["metrics"]["eva_summary_rows_pct.bytes"]["value"] < 100
    grid = json.loads(re.search(r"decode_grid=(\{.*?\})", out).group(1))
    assert 0 < grid["ticks"] <= grid["rows"] <= grid["grid_rows"]
    assert grid["grid_pages"] == 16 * grid["grid_rows"]


def test_the_configuration_keeps_every_published_width():
    _, config, traffic = bench_run.find_cell(BENCH, CELL)
    entry = next(c for c in BENCH["configs"] if c["name"] == "evabyte")
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert config["source"] == entry["source"]
    differs = {k for k, v in PUBLISHED.items() if config.get(k, "?") != v}
    assert differs == {"num_hidden_layers"}
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 8
    for key in ("pooling_scale", "pooled_key_bias", "pooling_after_rotary",
                "current_window", "prediction_heads", "rotary_pairing",
                "seeded_weights"):
        assert config["assumed"][key]
    assert "stage 0" in config["deployment"]
    # the cell's parameters, where ISSUE 36's table gives a number
    eng = traffic["engine"]
    assert traffic["prompt_tokens"] == dict(
        dist="lognormal", median=8192, sigma=0.6, min=2048, max=30720)
    assert traffic["output_tokens"] == dict(
        dist="lognormal", median=768, sigma=0.5, min=256, max=2048)
    assert traffic["max_total_tokens"] == 32768
    assert (traffic["lead_in_s"], traffic["reference_sample"],
            traffic["trace_seconds"], traffic["schedule_seed"]) \
        == (15.0, 4, 4.0, 0)
    assert (eng["policy"], eng["kv_dtype"], eng["block_size"],
            eng["prefill_chunk"], eng["chunk_rungs"], eng["batch_rungs"],
            eng["num_blocks"], eng["speculate_k"], max(eng["page_rungs"])) \
        == ("O5", "bf16", 16, 2048, [512, 1024, 2048], [8, 16], 4097, 0,
            256)
    assert traffic["rate_per_s"] == pytest.approx(
        traffic["knee_per_s"] * traffic["share_of_knee"])
    assert 1.25 <= traffic["share_of_knee"] <= 1.5


def test_the_engine_fills_the_rooflines_fact_while_a_profile_records():
    from apex_tpu.monitor import tracing

    _, config, traffic = bench_run.find_cell(BENCH, CELL)
    config.update(TINY_CONFIG)
    traffic["engine"].update(TINY_TRAFFIC["engine"])
    traffic["max_total_tokens"] = 128
    job = bench_run.resolve(config["builder"])(config, traffic, 5)
    sums = job.facts["tick_sums"]
    assert sums is job.engine.tick_sums
    assert sums["layers"] == 2 and "rows" not in sums
    job.engine.submit(job.make_request("r0", list(range(1, 71)), 6))
    for _ in range(4):       # admitted; chunks of 32, 32, 6 and a decode
        job.engine.step()
    assert "rows" not in sums          # ticks ran and nothing recorded
    tracing.set_tracer(tracing.SpanTracer())
    try:
        job.engine.step()
    finally:
        tracing.set_tracer(None)
    # the second decode tick: the query at position 71 of window 2 sees
    # 8 exact rows of its window and 2 x 8 pooled rows, in 2 layers
    assert sums["ticks"] == 1 and sums["rows"] == 1
    assert "pages_window" not in sums      # no sliding-window counters
    assert sums["eva_window_rows"] == 2 * 8
    assert sums["eva_summary_rows"] == 2 * 16
    assert sums["eva_rows"] == 2 * 24
    assert sums["eva_pages_live"] == 2 * 2 + 2
    assert sums["eva_pages_slots"] == 4 * 16
    flops, nbytes = eva_decode.ticks(**sums)
    assert flops == 4 * 4 * 16 * 48
    assert nbytes == 48 * 2 * 4 * 16 * 2 + 1 * 2 * 2 * 4 * 16 * 2


def test_eva_decode_counts_at_one_small_shape():
    """Two ticks of 3 and 2 live rows in 2 layers: 70 exact and 40
    pooled rows read in all, each a key and a value of 4 heads of 16 in
    bf16 and 4 x 4 x 16 flops; a q and an o a row and layer."""
    flops, nbytes = eva_decode.ticks(
        eva_window_rows=70, eva_summary_rows=40, rows=5, layers=2, heads=4,
        head_dim=16, eva_pages_live=99)               # others ignored
    assert flops == 110 * 4 * 4 * 16
    assert nbytes == 110 * 2 * 4 * 16 * 2 + 5 * 2 * 2 * 4 * 16 * 2


def test_eva_prefill_counts_at_one_small_shape():
    """A chunk of 32 bytes behind 8 pooled rows and one of 6 behind 16,
    2 layers of 4 heads of 16: the triangles once and every query with
    every pooled row; q, k, v, o of the bytes and k, v of the pooled
    rows."""
    pairs = 32 * 33 // 2 + 32 * 8 + 6 * 7 // 2 + 6 * 16
    flops, nbytes = eva_prefill.chunks(
        eva_chunk_pairs=pairs, eva_chunk_tokens=38,
        eva_chunk_summary_rows=24, eva_chunks=2, layers=2, heads=4,
        head_dim=16)
    assert flops == 2 * 4 * pairs * 4 * 16
    assert nbytes == 2 * 4 * 16 * 2 * (4 * 38 + 2 * 24)


def test_the_controls_run_through_the_kinds_check_at_tiny_sizes():
    """``benchmarks.control_evabyte`` end to end on the CPU: the float8
    engine, the cell as it is and the two planted faults each give a
    sample through the kind's ``reference_check``.  The limit is the
    published widths' (a tiny model's logits spread 0.3, not 0.8), so
    what is held here is that each control moves the number it is for;
    the readings that count are the chip's."""
    from benchmarks import control_evabyte

    _, config, traffic = bench_run.find_cell(BENCH, CELL)
    config.update(TINY_CONFIG)
    for key, value in TINY_TRAFFIC.items():
        traffic[key] = {**traffic[key], **value} \
            if isinstance(value, dict) else value
    job = bench_run.resolve(config["builder"])(config, traffic, 5)
    got = control_evabyte.controls(job, config, traffic, 5, 1.0)
    assert list(got) == ["float8", "change", "no_mu", "foreign_page"]
    assert got["change"]["correct"] and not got["change"]["faults"]
    for plant in ("float8", "no_mu", "foreign_page"):
        assert got[plant]["reference_mean_margin"] \
            > 3 * got["change"]["reference_mean_margin"] + 1e-4, plant
        assert got[plant]["reference_checked"] \
            == got["change"]["reference_checked"]
    # the plants are taken out again: the engine serves as it did
    assert job.engine.manager.block_table.__self__ is job.engine.manager


# the sample's largest margin as read on the chip (PERF.md section 6,
# PR 36): the largest the change gave over its fifteen runs, and each
# control's one reading (``benchmarks/control_evabyte.py``, seed 7)
READINGS = {
    "the change's largest reading": (0.02414, False),
    "float8 weights": (0.91056, True),
    "the pooled-key bias dropped": (1.92034, True),
    "a foreign page in the window": (2.17783, True),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_the_kinds_limit_stands_between_the_readings(name):
    """``serve_open_loop``'s ``LOGIT_MARGIN`` lies between what the
    change reads and what each control reads on this cell, with twofold
    room below and eighteenfold above: the accepted kind separates here
    and no kind of the cell's own was needed."""
    reading, refused = READINGS[name]
    assert (reading > kind.LOGIT_MARGIN) == refused
    assert not 0.5 * kind.LOGIT_MARGIN < reading < 2 * kind.LOGIT_MARGIN
