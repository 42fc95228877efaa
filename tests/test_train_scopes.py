"""The two scopes of the jitted train step (PR 27): every op of
``AmpOptimizer.apply_gradients`` carries ``apex.optimizer`` and the LM
head + loss carry ``apex.head_loss`` in their op names, forward and
backward, on both branches of the optimizer and in both smoke models --
and that is all they do: without the scopes the step lowers to the
same program, with as many kernel calls.

The benchmark's ``optimizer_ms.train`` and ``head_loss_ms.train`` read
these names from the device trace, so the patterns of their files are
held to the names here.
"""
import contextlib
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp

from apex_tpu.testing import standalone_bert, standalone_gpt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scope_pattern(metric):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)["params"]["scope_pattern"]


def build(model, pipeline):
    if model == "gpt":
        # the flash route, so the step holds kernel calls to count
        setup = standalone_gpt.make_smoke_setup(
            opt_level="O5", pipeline=pipeline, hidden=128, num_heads=2,
            seq=64, batch=2, dtype=jnp.bfloat16, use_flash=True)
        return standalone_gpt.build_train_step(setup), setup
    setup = standalone_bert.make_smoke_setup(opt_level="O5",
                                             pipeline=pipeline)
    return standalone_bert.build_train_step(setup), setup


def op_names(step, setup):
    """The framework op names of the lowered step (what XLA keeps as
    ``op_name`` and the profiler hands back as an op's scope)."""
    text = step.lower(setup.params, setup.amp_state).as_text(
        debug_info=True)
    return re.findall(r'loc\("(jit\(_step\)/[^"]*)"', text)


CASES = [(m, p) for m in ("gpt", "bert") for p in (False, True)]
IDS = [f"{m}-{'packed' if p else 'per_leaf'}" for m, p in CASES]


@pytest.mark.parametrize("model, pipeline", CASES, ids=IDS)
def test_step_op_names_hold_both_scopes(model, pipeline):
    step, setup = build(model, pipeline)
    names = op_names(step, setup)
    optimizer = [n for n in names
                 if re.search(scope_pattern("optimizer_ms.train"), n)]
    head_loss = [n for n in names
                 if re.search(scope_pattern("head_loss_ms.train"), n)]
    assert optimizer and head_loss
    # the optimizer runs after the backward pass, under no transform
    assert all(n.startswith("jit(_step)/apex.optimizer/")
               for n in optimizer)
    # every op that names either scope at all is one the patterns take
    assert len(optimizer) == sum("apex.optimizer" in n for n in names)
    assert len(head_loss) == sum("apex.head_loss" in n for n in names)
    # forward and backward, through the model and in the step's loss
    module = "GPTModel" if model == "gpt" else "BertModel"
    for head in (f"jit(_step)/jvp({module})/apex.head_loss/",
                 f"jit(_step)/transpose(jvp({module}))/apex.head_loss/",
                 "jit(_step)/jvp(apex.head_loss)/",
                 "jit(_step)/transpose(jvp(apex.head_loss))/"):
        assert any(n.startswith(head) for n in head_loss), head
    # the head's matmul and the master -> model cast are among them
    assert any(n.endswith("/dot_general") for n in head_loss)
    assert any(n.endswith("/convert_element_type") for n in optimizer)
    # and the two never claim one op
    assert not set(optimizer) & set(head_loss)


@pytest.mark.parametrize("model, pipeline", CASES, ids=IDS)
def test_scopes_are_metadata_only(model, pipeline, monkeypatch):
    step, setup = build(model, pipeline)
    scoped = step.lower(setup.params, setup.amp_state).as_text()
    scoped_calls = str(jax.make_jaxpr(step)(
        setup.params, setup.amp_state)).count("pallas_call")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_step, _ = build(model, pipeline)
    assert not any("apex." in n for n in op_names(bare_step, setup))
    bare = bare_step.lower(setup.params, setup.amp_state).as_text()
    assert scoped == bare
    assert scoped_calls == str(jax.make_jaxpr(bare_step)(
        setup.params, setup.amp_state)).count("pallas_call")
    if model == "gpt" or pipeline:
        assert scoped_calls > 0
