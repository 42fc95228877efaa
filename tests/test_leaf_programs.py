"""The amp optimizer steps every matrix leaf where it lies, through one
jitted leaf program a distinct leaf signature (``optimizers.fused_adam.
LeafProgram``), and its vectors (biases, norm scales) through one call
together: the lowered step holds one callee a matrix signature and one
for the vectors, so from 2 to 8 layers the optimizer's text grows by the
matrices' calls and the slices that cut the vectors back out; the
automatic route matches the packed ``pipeline=True`` step to the parity
tests' tolerances; and the optimizer records its route, its leaves and
its programs.
"""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.ops import fused_pipeline as fp
from apex_tpu.optimizers import fused_adam, fused_lamb, fused_sgd
from apex_tpu.testing import standalone_bert, standalone_gpt

from test_fused_pipeline import (L2_CONTRACTION_ULP, _policy, make_params,
                                 run_amp, tree_bitwise, unpacked_masters)


def gpt_setup(layers):
    return standalone_gpt.make_smoke_setup(
        opt_level="O5", num_layers=layers, hidden=128, num_heads=2,
        seq=64, batch=2, dtype=jnp.bfloat16, use_flash=True)


def signatures(params):
    """Distinct (shape, dtype) of the model's matrix leaves (rank 2 and
    up): under O5 the grad and model copy share the leaf's, the master
    and state are its fp32.  The vectors share one more callee."""
    return {(l.shape, l.dtype) for l in jax.tree_util.tree_leaves(params)
            if l.ndim >= 2}


def vectors(params):
    return sum(l.ndim < 2 for l in jax.tree_util.tree_leaves(params))


def lowered_optimizer(setup):
    grads = jax.tree_util.tree_map(jnp.zeros_like, setup.params)
    return jax.jit(setup.amp_opt.apply_gradients).lower(
        grads, setup.amp_state, setup.params).as_text()


def callees(text, prefix):
    """{name: signature and body} of the module's private functions
    named ``prefix*`` (the text after the name)."""
    out = {}
    for m in re.finditer(r"func\.func private @(\w+)\(", text):
        if m.group(1).startswith(prefix):
            out[m.group(1)] = text[m.end():text.index("\n  }", m.start())]
    return out


def main_ops(text):
    main = text[text.index("func.func public @main"):]
    main = main[:main.index("\n  }")]
    return collections.Counter(
        re.findall(r"stablehlo\.\w+|call @\w+", main.split("\n", 1)[1]))


def split_by_rank(bodies):
    """(matrix callees, vector callees): a callee's rank is its first
    argument's (the gradient's)."""
    def rank(body):
        return re.match(r"%arg0: tensor<([^>]*)>", body).group(1).count("x")
    return ([b for b in bodies.values() if rank(b) >= 2],
            [b for b in bodies.values() if rank(b) < 2])


@pytest.mark.parametrize("what", ["optimizer", "train_step"])
def test_one_callee_per_signature_and_calls_grow_with_depth(what):
    texts, setups = {}, {}
    for layers in (2, 8):
        setup = gpt_setup(layers)
        if what == "optimizer":
            texts[layers] = lowered_optimizer(setup)
        else:
            texts[layers] = standalone_gpt.build_train_step(setup).lower(
                setup.params, setup.amp_state).as_text()
        setups[layers] = setup
    (mat2, vec2), (mat8, vec8) = (
        split_by_rank(callees(texts[n], "adam_leaf_step")) for n in (2, 8))
    sigs = signatures(setups[8].params)
    # one callee a distinct matrix signature, the same ones at both
    # depths, and one for the vectors, longer at 8 layers
    assert len(mat8) == len(sigs) == len(signatures(setups[2].params))
    assert sorted(mat2) == sorted(mat8)
    assert len(vec2) == len(vec8) == 1 and vec2 != vec8
    assert setups[8].amp_opt.route == "per_leaf"
    leaves = jax.tree_util.tree_leaves(setups[8].params)
    assert setups[8].amp_opt.leaves_stepped == len(leaves)
    assert setups[8].amp_opt.leaf_programs == len(sigs) + 1
    if what == "optimizer":
        # the optimizer's own text grows by the matrices' calls, and by
        # the vectors' pieces: packed in, and each of the program's four
        # outputs sliced back out
        ops2, ops8 = main_ops(texts[2]), main_ops(texts[8])
        grown = {k for k in ops2.keys() | ops8.keys()
                 if ops2[k] != ops8[k]}
        assert grown and all(
            k.startswith("call @adam_leaf_step") or k in (
                "stablehlo.dynamic_slice", "stablehlo.concatenate",
                "stablehlo.constant") for k in grown)
        calls = sum(v for k, v in ops8.items()
                    if k.startswith("call @adam_leaf_step"))
        assert calls == len(leaves) - vectors(setups[8].params) + 1
        assert ops8["stablehlo.dynamic_slice"] \
            - ops2["stablehlo.dynamic_slice"] == 4 * (
                vectors(setups[8].params) - vectors(setups[2].params))


def test_lamb_step_holds_the_norm_program():
    """BERT's tree under FusedLAMB with its clip on, as the BERT cell
    steps it: a phase-1 leaf program and a one-output sum-of-squares
    program a matrix signature, and one of each for the vectors."""
    masters = standalone_bert.make_smoke_setup(
        opt_level="O5").amp_state.master_params
    params, opt, state = amp.initialize(masters, fused_lamb(1e-3),
                                        opt_level="O5")
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    text = jax.jit(opt.apply_gradients).lower(grads, state,
                                              params).as_text()
    sigs = signatures(params)
    mats, vecs = split_by_rank(callees(text, "lamb_leaf_step"))
    assert len(mats) == len(sigs) and len(vecs) == 1
    sumsq = callees(text, "leaf_sumsq")
    assert len(sumsq) == len(sigs) + 1
    assert all(re.search(r"-> tensor<f32>", body.split("\n", 1)[0])
               for body in sumsq.values())
    assert opt.leaf_programs == 2 * (len(sigs) + 1)


PARITY = {
    "adam": (lambda: fused_adam(1e-2, weight_decay=0.01), 64.0),
    "sgd": (lambda: fused_sgd(0.05, momentum=0.9, weight_decay=0.01),
            64.0),
    "lamb": (lambda: fused_lamb(1e-2, weight_decay=0.01,
                                max_grad_norm=1.0), 1.0),
}


def vector_params():
    """Matrices beside several vectors and a scalar: the vectors step in
    one call, packed and sliced back."""
    ks = jax.random.split(jax.random.PRNGKey(1), 7)
    return {
        "dense": {"kernel": jax.random.normal(ks[0], (9, 11)),
                  "bias": jax.random.normal(ks[1], (11,))},
        "norm": {"scale": 1.0 + jax.random.normal(ks[2], (11,)) * 0.1,
                 "bias": jax.random.normal(ks[3], (11,))},
        "out": jax.random.normal(ks[4], (7, 5)),
        "out_bias": jax.random.normal(ks[5], (5,)),
        "temperature": jax.random.normal(ks[6], ()),
    }


@pytest.mark.parametrize("tree", ["one_vector", "vectors"])
@pytest.mark.parametrize("name", sorted(PARITY))
def test_auto_route_matches_packed_pipeline(name, tree):
    """The automatic (per-leaf) route against the packed pipeline, held
    to the tolerance of that optimizer's parity test in
    test_fused_pipeline.py: Adam bitwise, SGD's L2 decay to one ulp on
    the masters, LAMB to rtol 1e-5 (its norms reduce in another
    order); on a tree with one vector, and on one whose vectors and
    scalar step in one call."""
    make_tx, scale = PARITY[name]
    params = make_params() if tree == "one_vector" else vector_params()
    policy = _policy(jnp.bfloat16, scale)
    m_auto, s_auto, _ = run_amp(make_tx, policy, params, pipeline=None)
    m_pack, s_pack, _ = run_amp(make_tx, policy, params, pipeline=True)
    assert not isinstance(s_auto.master_params, fp.PackedMasters)
    packed = unpacked_masters(s_pack, params)
    if name == "lamb":
        for a, b in zip(jax.tree_util.tree_leaves(packed),
                        jax.tree_util.tree_leaves(s_auto.master_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        return
    tree_bitwise(packed, s_auto.master_params, msg=name,
                 max_ulp=L2_CONTRACTION_ULP if name == "sgd" else 0)
    tree_bitwise(m_pack, m_auto, msg=f"model {name}")


def test_packed_route_records_no_leaf_programs():
    params = make_params()
    opt = amp.AmpOptimizer(fused_adam(1e-2), amp.get_policy("O5"),
                           pipeline=True)
    state = opt.init(params)
    model = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                   params)
    jax.jit(opt.apply_gradients).lower(model, state, model)
    assert opt.route == "packed"
    assert opt.leaves_stepped == 3
    assert opt.leaf_programs == 0
