"""``kind: serve_open_loop_routed`` -- ``serve_open_loop`` for a model
whose tokens pass through top-k routed experts.  The drive, the
measures, the facts, the ``[bench]`` lines and the hold of the decode
grid to the trace are the accepted kind's own code, called as it
stands; what differs is what the reference's margins are held to.

Why it differs.  The accepted kind holds the *largest* margin of a few
hundred sampled tokens to ``LOGIT_MARGIN`` = 0.05: the reference logit
of the token the system emitted may lie that far under the reference's
largest.  A bf16 forward of a dense model lands within that (its noise
is a hundredth of the logits' spread).  A top-k router is a step
function: where the float32 reference's k-th and (k+1)-th expert scores
lie closer together than bf16's noise in the router's input, a correct
bf16 forward picks the other expert about half the time, and the logits
move by a whole expert's output.  With 256 experts, 8 a token and four
routed layers that is no rare place: at the published widths of
``laguna-xs2`` the chip's bf16 forward chose another expert set than the
float32 reference at 29% of 2,048 positions (7-23% a layer); margins
read 0.028 at most where no expert differed and up to 0.62 where one
did, against a spread of the logits of 0.91 (``PERF.md`` section 6,
PR 29, every reading).  The reference's own score gaps do not single
those positions out either: swaps occurred at gaps up to 0.0038, and
90% of the positions have a smaller gap in one of their four layers.
So no limit on the largest margin near 0.05 can hold, and one wide
enough to hold says little by itself.

What is held instead is the sample's distribution, three limits, each
between what the change reads and what a fault reads:

* every token within ``TOKEN_MARGIN``: a token from a wrong position, a
  stale or foreign cache page, or a row whose experts were dropped is a
  token the reference never favoured, 5 to 6 under its largest logit
  where one was planted; the swaps read 0.90 at most;
* the mean margin within ``MEAN_MARGIN`` and
* the share of tokens over the accepted ``LOGIT_MARGIN`` within
  ``OVER_SHARE``: a forward in less than bf16 swaps experts nearly
  everywhere and shows in both.
"""
from __future__ import annotations

import contextlib

import numpy as np

from ..common import check
from . import serve_open_loop as base

LOGIT_MARGIN = base.LOGIT_MARGIN
# Each between two readings on the chip (PERF.md section 6, PR 29), near
# their geometric middle: the largest the change gave over twenty-six
# runs of the cell and three longer forwards (0.90, 0.0216, 0.098), and
# its control put through this check on the served path by
# ``benchmarks/control_routed.py``: the engine serving its weights
# rounded once more to float8_e4m3 (mean 0.161, share 0.470; 0.159-0.171
# and 0.486-0.500 in three forwards without the cache: refused by both)
# and, for the per-token limit, one emitted token replaced by a random
# one (5.6 and 6.3 under the reference's largest: refused by that limit
# alone).  tests/benchmark/test_laguna_cell.py holds the limits between
# these readings.
TOKEN_MARGIN = 1.8
MEAN_MARGIN = 0.05
OVER_SHARE = 0.2


def sampled(traffic, tracks, seed) -> list:
    """The accepted kind's sample: the same seeded picks among the
    finished requests of the window."""
    done = [t.request for t in tracks if t.in_window
            and t.request.terminal == "finished"]
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    picks = rng.choice(len(done), min(traffic["reference_sample"],
                                      len(done)), replace=False) \
        if done else []
    return [done[int(i)] for i in picks]


def reference_check(job, traffic, tracks, seed, faults) -> dict:
    """The accepted kind's sample, prompt + output in one reference
    forward each, held to the three limits above."""
    picks = sampled(traffic, tracks, seed)
    width = traffic["max_total_tokens"]
    margins, spreads = [], []
    for r in picks:
        seq = list(r.prompt) + list(r.out_tokens)
        tokens = np.zeros((1, width), np.int32)
        emitted = np.zeros((1, width), np.int32)
        tokens[0, :len(seq)] = seq
        emitted[0, :len(seq) - 1] = seq[1:]
        m, s = job.reference_margins(tokens, emitted)
        lo, hi = len(r.prompt) - 1, len(seq) - 1
        margins.append(np.asarray(m)[0, lo:hi])
        spreads.append(np.asarray(s)[0, lo:hi])
    check(faults, bool(margins), "no finished request to hold against "
                                 "the reference")
    if not margins:
        return dict(reference_checked="0req/0tok")
    m = np.concatenate(margins)
    worst, mean = float(m.max()), float(m.mean())
    over = float((m > LOGIT_MARGIN).mean())
    check(faults, worst <= TOKEN_MARGIN,
          f"an emitted token lies {worst} under the reference's largest "
          f"logit (TOKEN_MARGIN {TOKEN_MARGIN})")
    check(faults, mean <= MEAN_MARGIN,
          f"emitted tokens lie {mean} under the reference's largest "
          f"logit on average (MEAN_MARGIN {MEAN_MARGIN})")
    check(faults, over <= OVER_SHARE,
          f"{over:.3f} of the emitted tokens lie more than "
          f"{LOGIT_MARGIN} under the reference's largest logit "
          f"(OVER_SHARE {OVER_SHARE})")
    return dict(reference_checked=f"{len(picks)}req/{m.size}tok",
                reference_argmax=int((m == 0).sum()),
                reference_max_margin=round(worst, 5),
                reference_mean_margin=round(mean, 6),
                reference_over_share=round(over, 4),
                reference_logit_spread=float(
                    np.concatenate(spreads).mean()))


@contextlib.contextmanager
def _checking_with(check_fn):
    """The accepted kind's ``run`` looks its reference check up by name
    when it reaches it, and its file may not be edited to take one as an
    argument: stand this module's in for the length of a run."""
    was = base.reference_check
    base.reference_check = check_fn
    try:
        yield
    finally:
        base.reference_check = was


def run(job, traffic, **kwargs):
    with _checking_with(reference_check):
        return base.run(job, traffic, **kwargs)
