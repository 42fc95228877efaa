"""The controls of ``kind: serve_open_loop_routed``'s three limits, put
through the kind's own comparison on the served path.  Run once, by
hand, on the chip, when the limits are set or moved (``PERF.md`` holds
the readings); no part of a measured run.

    python3 -m benchmarks.control_routed \
        --workload laguna-xs2.serve-code-sat --seed 7 --seconds 20

One process, one engine, the cell's own traffic, three samples through
``reference_check``:

* ``float8``: the engine serves its bf16 weights rounded once more to
  float8_e4m3 (the nearest precision below the one the configuration
  states), the reference holds the weights as made: has to be refused,
  by ``MEAN_MARGIN`` and by ``OVER_SHARE``;
* ``change``: the cell as it is: has to be correct;
* ``foreign``: the same sample with the last emitted token of ONE
  request replaced by a token drawn at random -- what a wrong position,
  a stale or foreign cache page or a row whose experts were dropped
  emits, a token the reference never favoured: has to be refused by
  ``TOKEN_MARGIN`` alone.
"""
from __future__ import annotations

import argparse
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from . import builders_laguna, reference_laguna
from . import run as bench_run
from .common import say
from .kinds import serve_open_loop_routed as routed
from .kinds.serve_open_loop import drive


def _serve(job, traffic, seed, seconds, tag):
    """One driven span on an empty engine, followed to its end so that
    the next starts empty too."""
    make = job.make_request
    job.make_request = lambda rid, prompt, n: make(f"{tag}:{rid}", prompt, n)
    try:
        return drive(job, dict(traffic, follow_to_completion=True),
                     seed=seed, seconds=seconds).tracks
    finally:
        job.make_request = make


def _held(job, traffic, tracks, seed) -> dict:
    faults = []
    ref = routed.reference_check(job, traffic, tracks, seed, faults)
    return dict(ref, correct=not faults, faults=faults)


def controls(job, config, traffic, seed, seconds) -> dict:
    """{control: the kind's reference facts, ``correct``, ``faults``}."""
    engine = job.engine
    engine.warmup()
    # leaf by leaf, each old leaf let go as its rounded twin is made:
    # the chip does not hold the weights twice.  (Two programs a leaf:
    # inside one, the TPU compiler drops the round trip through a type
    # the v5e has no unit for.)  The builder's reference closes over
    # the buffers deleted here, so the reference of this run is made
    # again from the seed, once the rounded ones are let go
    def lower(w):
        if w.dtype != jnp.bfloat16:
            return w
        low = w.astype(jnp.float8_e4m3fn)
        w.delete()
        return low.astype(jnp.bfloat16)

    engine.weights = jax.tree.map(lower, engine.weights)
    tracks = _serve(job, traffic, seed, seconds, "float8")
    engine.weights = None
    weights = builders_laguna.make_weights(config, engine.model_cfg, seed)
    margins = jax.jit(functools.partial(reference_laguna.margins,
                                        config=config))
    job.reference_margins = lambda tokens, emitted: margins(
        weights, tokens, emitted)[:2]
    out = {"float8": _held(job, traffic, tracks, seed)}

    engine.weights = weights
    tracks = _serve(job, traffic, seed, seconds, "change")
    out["change"] = _held(job, traffic, tracks, seed)

    victim = routed.sampled(traffic, tracks, seed)[0]
    was = victim.out_tokens[-1]
    rng = np.random.default_rng([int(seed), 0xF0E1])
    victim.out_tokens[-1] = int(
        (was + rng.integers(1, job.vocab)) % job.vocab)
    out["foreign"] = _held(job, traffic, tracks, seed)
    victim.out_tokens[-1] = was
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("[bench] no TPU: the limits stand between readings on the "
              "chip", file=sys.stderr)
        return 1
    from apex_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    bench = bench_run.load_json(bench_run.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.find_cell(bench, args.workload)
    job = bench_run.resolve(config["builder"])(config, traffic, args.seed)
    for name, held in controls(job, config, traffic, args.seed,
                               args.seconds).items():
        say(control=name, **held)
    return 0


if __name__ == "__main__":
    sys.exit(main())
