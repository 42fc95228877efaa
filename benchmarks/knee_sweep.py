"""Find the knee of a serving cell's traffic mix: the highest arrival
rate the engine sustains without a growing backlog.  Run once, by hand,
on the chip, when a cell is defined (README.md says how the result is
used); no part of a measured run.

    python3 -m benchmarks.knee_sweep --workload gpt2-345m.serve-chat \
        --seed 7 --seconds 25 --rates 3 3.5 4 4.5 6

One process holds the engine and offers the cell's mix at each rate in
turn, each time with a lead-in, for ``--seconds``, then follows every
request to its end so the next rate starts on an empty engine.  A rate
is *sustained* when the queue at the window's close is no longer than
at its middle (or at most 2 requests) and the generator ran less than
one decode tick late; the knee lies between the highest sustained rate
and the lowest that was not.
"""
from __future__ import annotations

import argparse
import sys
import time

from . import run as bench_run
from .common import say
from .kinds.serve_open_loop import drive, measures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("[bench] no TPU: a knee is a device number", file=sys.stderr)
        return 1
    from apex_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.find_cell(bench, args.workload)
    job = bench_run.resolve(config["builder"])(config, traffic, args.seed)
    job.engine.warmup()
    make_request = job.make_request
    for rate in args.rates:
        job.make_request = lambda rid, prompt, n, _r=rate: make_request(
            f"{_r}:{rid}", prompt, n)
        mix = dict(traffic, rate_per_s=rate, follow_to_completion=True)
        d = drive(job, mix, seed=args.seed, seconds=args.seconds)
        drained = time.perf_counter()
        m = measures(d)
        late = max(d.lateness_s, default=0.0)
        say(rate_per_s=rate, requests=sum(t.in_window for t in d.tracks),
            queue_mid=d.queue_mid, queue_close=d.queue_close,
            active_close=d.active_close,
            sustained=d.queue_close <= max(d.queue_mid, 2),
            ttft_p50_ms=round(m["ttft_p50_ms"], 1),
            ttft_p90_ms=round(m["ttft_p90_ms"], 1),
            itl_p50_ms=round(m["itl_p50_ms"], 1),
            itl_p95_ms=round(m["itl_p95_ms"], 1),
            tokens_per_s=round(m["serve_tokens_per_s"], 1),
            lateness_max_ms=round(1e3 * late, 1),
            drain_s=round(drained - d.closed, 1),
            compiles_in_window=d.compiles_in_window)
    return 0


if __name__ == "__main__":
    sys.exit(main())
