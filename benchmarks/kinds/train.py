"""``kind: train`` -- the synchronous training loop: one jitted step
after another on the job's fixed batch, each ending in a host read of
its loss (the shape of ``run_monitored_steps``).

Set-up: the reference loss on the initial weights, ONE lowering and
compilation of the step (its text, for the kernel count, is taken from
the executable that then runs), ``warmup_steps`` steps.  The window
opens before a step and closes at the first loss read at or after
``--seconds``: every step started in it completed in it, and the rate
is all those tokens over all that time.
"""
from __future__ import annotations

import math
import time

import jax

from ..common import CellResult, CompileCounter, check, say
from ..trace import load_trace

# A bf16 forward against the float32 reference: each logit carries a
# relative error near 2**-8, but the loss is a mean over thousands of
# tokens and the errors do not line up; measured differences are near
# 1e-4 (PR 23: 11.0276 against 11.0275).  A forward in a lower
# precision than bf16, a wrong mask or a dropped layer moves the loss
# of random weights by more than 0.05.
LOSS_TOLERANCE = 0.02


def run(job, traffic, *, seed, seconds, trace_dir, platform,
        peaks) -> CellResult:
    faults = []
    loss_reference = job.reference_loss()     # before the step donates
    t0 = time.perf_counter()
    compiled = job.step.lower(job.params, job.amp_state).compile()
    lower_compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    check(faults, kernels > 0 or platform != "tpu",
          "the step holds no Mosaic kernel: a silent jnp twin")

    params, amp_state = job.params, job.amp_state
    losses, ends = [], []

    def one_step(span="bench."):
        nonlocal params, amp_state
        with jax.profiler.TraceAnnotation(span + "step"):
            params, amp_state, loss, _, _ = compiled(params, amp_state)
        with jax.profiler.TraceAnnotation(span + "loss_read"):
            losses.append(float(loss))
        ends.append(time.perf_counter())

    for _ in range(traffic["warmup_steps"]):
        one_step()
    trace_at = 3 if trace_dir else None       # window steps before it
    traced = False
    with CompileCounter() as compiles:
        opened = time.perf_counter()
        steps = 0
        while True:
            if steps == trace_at:
                # the profiler's first step starts late (70 ms seen on the
                # chip): it runs under another span name, so the traced
                # window (the bench.* spans) opens on the step after it
                jax.profiler.start_trace(trace_dir)
                one_step(span="settle.")
                steps += 1
            one_step()
            steps += 1
            if trace_at is not None and steps == trace_at + 1 \
                    + traffic["trace_steps"]:
                jax.profiler.stop_trace()
                traced = True
            window_s = time.perf_counter() - opened
            if window_s >= seconds and (trace_at is None
                                        or traced):
                break
        compiles_in_window = compiles.count

    window_losses = losses[traffic["warmup_steps"]:]
    not_finite = sum(1 for x in losses if not math.isfinite(x))
    check(faults, not_finite == 0, f"{not_finite} non-finite losses")
    check(faults, abs(losses[0] - loss_reference) <= LOSS_TOLERANCE,
          f"first-step loss {losses[0]} against the reference's "
          f"{loss_reference}: off by more than {LOSS_TOLERANCE}")
    check(faults, losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    check(faults, compiles_in_window == 0,
          f"{compiles_in_window} programs lowered inside the window")
    rate = steps * job.tokens_per_step / window_s
    # of the host's window, for the [bench] line alone: a traced run's
    # window holds the profiler's stalls, so the metric ``mfu_pct.train``
    # is read from the trace (readers/step_mfu.py), not from here
    mfu_pct = 100 * rate * job.flops_per_token / peaks["bf16_flops_per_s"]
    # one far-off run in ten was seen on the chip (PR 24): these two say
    # whether a run's steps were all a little slow or a few stalled
    took = sorted(b - a for a, b in zip(ends[-steps - 1:], ends[-steps:]))
    median = took[len(took) // 2]
    say(params=job.n_params, tokens_per_step=job.tokens_per_step,
        steps=steps, window_s=round(window_s, 4),
        step_ms=round(1e3 * window_s / steps, 3),
        step_ms_median=round(1e3 * median, 3),
        step_ms_max=round(1e3 * took[-1], 3),
        steps_over_1p5_median=sum(1 for t in took if t > 1.5 * median),
        loss_first=round(losses[0], 4),
        loss_reference=round(loss_reference, 4),
        loss_last=round(losses[-1], 4), kernels_in_step=kernels,
        compiles_in_window=compiles_in_window,
        lower_compile_s=round(lower_compile_s, 1),
        flops_per_token=f"{job.flops_per_token:.4g}",
        mfu_pct=round(mfu_pct, 2))
    return CellResult(
        correct=not faults, attempted=steps,
        failed=sum(1 for x in window_losses if not math.isfinite(x)),
        end_to_end={"train_tokens_per_s": rate},
        window_opened_at=opened,
        facts=dict(job.facts,
                   step_flops=job.tokens_per_step * job.flops_per_token),
        trace=load_trace(trace_dir) if trace_dir else None,
        faults=faults)
