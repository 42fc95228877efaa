"""The controls of the ``evabyte`` cell's comparison, put through its
kind's own ``reference_check`` on the served path.  Run once, by hand,
on the chip, when the cell is defined or its limit moved (``PERF.md``
holds the readings); no part of a measured run.

    python3 -m benchmarks.control_evabyte \
        --workload evabyte.serve-bytes-sat --seed 7 --seconds 20 [--rate 0.5]

One process, one engine, the cell's own traffic, four samples:

* ``float8``: the engine serves its bf16 weights rounded once more to
  float8_e4m3 (the nearest precision below the one the configuration
  states), the reference holds the weights as made: has to be refused;
* ``change``: the cell as it is: has to be correct;
* ``no_mu``: a fault of the mechanism planted in the weights the engine
  holds: every layer's pooled-key bias ``mu`` zeroed, so every pooled
  row is scored wrongly (a wrong pooling): has to be refused;
* ``foreign_page``: a fault planted in the block tables the engine
  builds: the first page of every row's window is read from the dump
  page, rows another owner left there, as a page freed too early would
  hold: has to be refused.

The plants touch the host's tables and the weight tree alone: the
programs are the cell's own, compiled once.
"""
from __future__ import annotations

import argparse
import functools
import importlib
import sys

import jax
import jax.numpy as jnp

from . import builders_evabyte, reference_evabyte
from . import run as bench_run
from .common import say
from .control_routed import _serve


def _held(check, job, traffic, tracks, seed) -> dict:
    faults = []
    ref = check(job, traffic, tracks, seed, faults)
    return dict(ref, correct=not faults, faults=faults)


def controls(job, config, traffic, seed, seconds) -> dict:
    """{control: the kind's reference facts, ``correct``, ``faults``}."""
    check = importlib.import_module(
        f"benchmarks.kinds.{traffic['kind']}").reference_check
    held = functools.partial(_held, check, job, traffic)
    engine = job.engine
    engine.warmup()

    # leaf by leaf, each old leaf let go as its rounded twin is made:
    # the chip does not hold the weights twice.  (Two programs a leaf:
    # inside one, the TPU compiler drops the round trip through a type
    # the v5e has no unit for.)  The builder's reference closes over
    # the buffers deleted here, so the reference is made again from the
    # seed once the rounded ones are let go
    def lower(w):
        if w.dtype != jnp.bfloat16:
            return w
        low = w.astype(jnp.float8_e4m3fn)
        w.delete()
        return low.astype(jnp.bfloat16)

    engine.weights = jax.tree.map(lower, engine.weights)
    tracks = _serve(job, traffic, seed, seconds, "float8")
    engine.weights = None
    weights = builders_evabyte.make_weights(config, engine.model_cfg, seed)
    margins = jax.jit(functools.partial(reference_evabyte.margins,
                                        config=config))
    job.reference_margins = lambda tokens, emitted: margins(
        weights, tokens, emitted)
    out = {"float8": held(tracks, seed)}

    engine.weights = weights
    out["change"] = held(_serve(job, traffic, seed, seconds, "change"),
                         seed)

    engine.weights = weights._replace(layers=tuple(
        lw._replace(mu=jnp.zeros_like(lw.mu)) for lw in weights.layers))
    out["no_mu"] = held(_serve(job, traffic, seed, seconds, "no_mu"), seed)
    engine.weights = weights

    manager = engine.manager
    table = manager.block_table

    def foreign(rid, max_pages):
        row = table(rid, max_pages)
        first = manager.num_pages(rid) - len(manager.blocks(rid))
        if manager.blocks(rid):
            row[first] = 0               # the dump page
        return row

    manager.block_table = foreign
    try:
        out["foreign_page"] = held(
            _serve(job, traffic, seed, seconds, "foreign"), seed)
    finally:
        del manager.block_table
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="requests/s offered (default: the cell's own; a "
                         "rate under the knee drains sooner)")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("[bench] no TPU: the limit stands between readings on the "
              "chip", file=sys.stderr)
        return 1
    from apex_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.find_cell(bench, args.workload)
    if args.rate:
        traffic["rate_per_s"] = args.rate
    job = bench_run.resolve(config["builder"])(config, traffic, args.seed)
    for name, got in controls(job, config, traffic, args.seed,
                              args.seconds).items():
        say(control=name, **got)
    say(used_blocks_hw=job.engine.manager.used_blocks_hw,
        usable_blocks=job.engine.cache_cfg.usable_blocks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
