"""Expert parallelism: Switch-style MoE with all-to-all token dispatch.

The reference has no MoE (SURVEY §2.10: "EP: not present anywhere");
this module completes the parallelism alphabet (dp/tp/pp/sp/**ep**) the
framework's mesh registry reserves.  Design follows Switch Transformer
(Fedus et al. 2021) / GShard dispatch algebra, TPU-first:

- experts are sharded over a mesh axis (one or more experts per shard);
- a top-1 router assigns each token an expert and a gate probability;
- tokens are packed into a fixed-capacity ``(experts, capacity, h)``
  dispatch buffer (static shapes — XLA requirement; overflow tokens are
  dropped, the standard capacity-factor contract) and exchanged with
  ``all_to_all`` over ICI;
- the exchange is **overlapped** (ISSUE-19): the buffer is chunked
  along capacity (``APEX_TPU_MOE_A2A_CHUNKS``, default 2) and chunk
  ``i+1``'s all_to_all is double-buffered against chunk ``i``'s expert
  matmul, so dispatch latency hides behind compute and the APX704
  overlap advisory goes quiet; ``a2a_chunks=1`` restores the legacy
  single-shot exchange (and the advisory — the un-overlapped trace is
  kept as the regression fixture);
- routing + slotting + the buffer scatter run through the fused Pallas
  kernel (:mod:`apex_tpu.ops.moe_routing`, jnp twin off TPU) when
  ``APEX_TPU_MOE_FUSED_DISPATCH`` is on (default) — bit-identical
  keep/slot decisions either way;
- the combine scatter multiplies by the gate so router gradients flow.

Everything runs inside ``shard_map`` over ``axis_name``; capacity math
is per-shard static.
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
from .._compat import axis_size
import jax.numpy as jnp

from ..analysis.flags import flag_bool, flag_int
from ..mesh_plan import MeshPlan
from ..ops.moe_routing import moe_combine, moe_route_dispatch
from ..parallel_state import EXPERT_AXIS  # noqa: F401


class RouterOutput(NamedTuple):
    expert_index: jnp.ndarray   # (T,) int32 chosen expert per token
    gate: jnp.ndarray           # (T,) f32 chosen-expert probability
    load_balancing_loss: jnp.ndarray  # scalar aux loss (Switch eq. 4)


def top1_router(logits: jnp.ndarray) -> RouterOutput:
    """Top-1 gating with the Switch load-balancing auxiliary loss."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
    num_experts = logits.shape[-1]
    # fraction of tokens per expert x mean router prob per expert
    frac = jnp.mean(
        jax.nn.one_hot(idx, num_experts, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    return RouterOutput(idx.astype(jnp.int32), gate, aux)


class Top2RouterOutput(NamedTuple):
    expert_index: jnp.ndarray   # (2, T) int32 chosen experts per token
    gate: jnp.ndarray           # (2, T) f32 normalized gates
    load_balancing_loss: jnp.ndarray  # scalar aux loss


def top2_router(logits: jnp.ndarray,
                second_policy: str = "all",
                rng: Optional[jax.Array] = None) -> Top2RouterOutput:
    """Top-2 gating with the GShard algebra the module docstring cites
    (Lepikhin et al. 2020, eq. for Algorithm 1): each token routes to
    its two highest-probability experts, gates renormalized over the
    pair; the auxiliary loss is the top-1 fraction x mean-prob product
    (the differentiable load estimator, GShard l_aux).

    ``second_policy``: ``"all"`` always keeps the second expert;
    ``"random"`` keeps it with probability ``min(1, 2 * gate2)`` (the
    GShard Algorithm-1 dispatch-saving trick: confident-second tokens
    always dispatch, marginal ones dispatch proportionally, and E[kept
    dispatches] halves at the uniform-gate worst case).  ``rng`` is
    required for "random" — the draw is a pure function of the key, so
    the policy stays deterministic per key.  A dropped second choice
    carries gate 0, which :func:`moe_dispatch_combine` treats as
    "do not dispatch": it claims NO capacity slot (the saving) and
    contributes nothing to the combine.
    """
    if second_policy not in ("all", "random"):
        raise ValueError(
            f"second_policy must be 'all'|'random', got "
            f"{second_policy!r}")
    if second_policy == "random" and rng is None:
        raise ValueError("second_policy='random' requires rng")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    num_experts = logits.shape[-1]
    idx1 = jnp.argmax(probs, axis=-1)
    gate1 = jnp.take_along_axis(probs, idx1[:, None], axis=1)[:, 0]
    masked = probs * (1.0 - jax.nn.one_hot(idx1, num_experts,
                                           dtype=probs.dtype))
    idx2 = jnp.argmax(masked, axis=-1)
    gate2 = jnp.take_along_axis(masked, idx2[:, None], axis=1)[:, 0]
    denom = jnp.maximum(gate1 + gate2, 1e-9)
    # aux loss over the FIRST choice (GShard: top-2's second choice is
    # excluded from the load estimator)
    frac = jnp.mean(
        jax.nn.one_hot(idx1, num_experts, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac * mean_prob)
    g1n, g2n = gate1 / denom, gate2 / denom
    if second_policy == "random":
        u = jax.random.uniform(rng, g2n.shape)
        # stop_gradient on the threshold: the Bernoulli draw is not a
        # differentiable path (GShard treats it as a dispatch decision,
        # not a gate transformation)
        keep2 = u < jax.lax.stop_gradient(2.0 * g2n)
        g2n = jnp.where(keep2, g2n, 0.0)
    return Top2RouterOutput(
        jnp.stack([idx1, idx2]).astype(jnp.int32),
        jnp.stack([g1n, g2n]), aux)


def _dispatch_indices(expert_index: jnp.ndarray, num_experts: int,
                      capacity: int, valid=None):
    """Position of each token within its expert's capacity slots.

    Returns ``(slot, keep)``: slot in [0, capacity) and a keep mask
    (False = dropped by overflow or invalid).  Pure cumsum arithmetic —
    no sorting, no dynamic shapes.  ``valid`` (bool (T,)) marks entries
    that should not dispatch at all (e.g. second choices dropped by the
    GShard "random" policy): they claim NO slot — later entries slide
    into the freed capacity — and come back keep=False.
    """
    one_hot = jax.nn.one_hot(expert_index, num_experts, dtype=jnp.int32)
    if valid is not None:
        one_hot = one_hot * valid.astype(jnp.int32)[:, None]
    position_in_expert = jnp.cumsum(one_hot, axis=0) * one_hot  # 1-based
    # a dispatching entry's own one-hot contributes 1 to its cumsum, so
    # its slot is >= 0; invalid entries have an all-zero row -> slot -1
    slot = jnp.sum(position_in_expert, axis=1) - 1               # (T,)
    keep = (slot >= 0) & (slot < capacity)
    return jnp.clip(slot, 0, capacity - 1), keep


def _resolve_chunks(a2a_chunks: Optional[int]) -> int:
    """``None`` defers to APEX_TPU_MOE_A2A_CHUNKS (default 2: the
    overlapped schedule); an explicit int wins."""
    return (flag_int("APEX_TPU_MOE_A2A_CHUNKS") if a2a_chunks is None
            else int(a2a_chunks))


def _chunked_expert_exchange(buf: jnp.ndarray,
                             expert_fn: Callable,
                             axis_name: str,
                             chunks: int
                             ) -> Tuple[List[jnp.ndarray], int]:
    """Overlapped dispatch/compute/return schedule (ISSUE-19).

    Splits the ``(E, capacity, H)`` dispatch buffer into ``chunks``
    equal capacity slices and traces, in order: every dispatch
    all_to_all back-to-back, then per chunk the expert compute and its
    return all_to_all.  The trace order IS the overlap structure
    (APX704's linear-order model): no collective's output is consumed
    by the immediately following equation — each dispatch a2a is
    followed by the next chunk's a2a, and chunk ``i``'s return a2a is
    followed by chunk ``i+1``'s expert matmul on an already-arrived
    chunk, so every transfer has independent compute to hide behind.

    The backward is hand-scheduled too (custom_vjp): AD's transpose
    would emit each transposed a2a immediately before the transposed
    expert matmul that consumes it — re-tightening the very schedule
    the forward loosened — so the bwd rule mirrors the forward order
    on cotangents: every return-transpose a2a back-to-back, then per
    chunk the expert VJP and its dispatch-transpose a2a.  The expert
    closure's captured tracers (wi/wo under grad) become explicit
    custom_vjp operands via ``jax.closure_convert`` so their gradients
    survive the custom rule.  Differentiating under ``shard_map``
    requires ``check_vma=False`` (as every committed entry point
    already traces): the replication-rewrite machinery on this jax
    predates nested ``jax.vjp`` inside a custom rule.

    Returns ``(return_chunks, chunk_capacity)``; the caller combines
    per chunk (:func:`_chunked_combine`) — concatenating here would
    plant a consumer right behind the last return collective.
    """
    e, c, h = buf.shape
    cs = -(-c // chunks)
    if chunks * cs != c:
        buf = jnp.pad(buf, ((0, 0), (0, chunks * cs - c), (0, 0)))
    n_shards = axis_size(axis_name)
    # Type-only example of an arrived chunk, cut from buf so that it
    # varies over the axes buf varies over: traced on an unvarying
    # ShapeDtypeStruct the closure bakes in a pvary that the replay on
    # the real (already varying) chunk rejects.
    piece = buf[:, :cs].reshape(e // n_shards, cs * n_shards, h)
    closed, consts = jax.closure_convert(expert_fn, piece)

    def _disp(p):   # dispatch hop; also the transpose of _ret
        return jax.lax.all_to_all(p, axis_name, split_axis=0,
                                  concat_axis=1, tiled=True)

    def _ret(y):    # return hop; also the transpose of _disp
        return jax.lax.all_to_all(y, axis_name, split_axis=1,
                                  concat_axis=0, tiled=True)

    @jax.custom_vjp
    def run(buf, *consts):
        pieces = [buf[:, i * cs:(i + 1) * cs] for i in range(chunks)]
        arrived = [_disp(p) for p in pieces]
        return tuple(_ret(closed(d, *consts)) for d in arrived)

    def run_fwd(buf, *consts):
        pieces = [buf[:, i * cs:(i + 1) * cs] for i in range(chunks)]
        arrived = [_disp(p) for p in pieces]
        returns, pulls = [], []
        for d in arrived:
            y, pull = jax.vjp(closed, d, *consts)
            returns.append(_ret(y))
            pulls.append(pull)
        return tuple(returns), tuple(pulls)

    def run_bwd(pulls, cts):
        # mirror the forward: all return-transposes in flight first...
        ct_arrived = [_disp(ct) for ct in cts]
        ct_pieces, ct_consts = [], None
        for i, co in enumerate(ct_arrived):
            parts = pulls[i](co)    # chunk i+1's VJP compute trails
            ct_pieces.append(_ret(parts[0]))  # ...chunk i's a2a here
            rest = parts[1:]
            ct_consts = (list(rest) if ct_consts is None else
                         [jax.tree_util.tree_map(jnp.add, a, b)
                          for a, b in zip(ct_consts, rest)])
        ct_buf = jnp.concatenate(ct_pieces, axis=1)
        return (ct_buf,) + tuple(ct_consts)

    run.defvjp(run_fwd, run_bwd)
    return list(run(buf, *consts)), cs


def _chunked_combine(returns: List[jnp.ndarray], cs: int,
                     expert_index: jnp.ndarray, gate: jnp.ndarray,
                     slot: jnp.ndarray, keep: jnp.ndarray,
                     out_dtype) -> jnp.ndarray:
    """Per-chunk gate-weighted gather, accumulated in fp32.  Exactly
    one chunk holds each kept entry's slot, so the masked sum equals
    the single-buffer combine bit-for-bit.  The gate masking is traced
    FIRST — it is independent of every return chunk, which is what
    keeps the last return all_to_all overlappable."""
    k, t = expert_index.shape
    idx_flat = expert_index.reshape(-1)
    g = jnp.where(keep, gate.reshape(-1), 0.0).astype(jnp.float32)
    h = returns[0].shape[-1]
    acc = jnp.zeros((k * t, h), jnp.float32)
    for i, r in enumerate(returns):
        local = jnp.clip(slot - i * cs, 0, cs - 1)
        in_chunk = (slot >= i * cs) & (slot < (i + 1) * cs)
        tok = r[idx_flat, local].astype(jnp.float32)
        acc = acc + jnp.where(in_chunk[:, None], tok * g[:, None], 0.0)
    return acc.reshape(k, t, h).sum(0).astype(out_dtype)


def moe_dispatch_combine(x: jnp.ndarray,
                         router: RouterOutput,
                         expert_fn: Callable[[jnp.ndarray], jnp.ndarray],
                         num_experts: int,
                         capacity_factor: float = 1.25,
                         axis_name: Optional[str] = EXPERT_AXIS,
                         a2a_chunks: Optional[int] = None
                         ) -> jnp.ndarray:
    """Dispatch tokens to experts, apply, combine.

    ``x``: (T, H) local tokens.  ``expert_fn`` maps the LOCAL experts'
    buffer ``(local_experts, rows, H) -> same`` (vmapped expert MLP).
    With ``axis_name`` the global experts are sharded over that axis
    (``num_experts %% axis_size == 0``) and dispatch/return ride
    capacity-chunked ``all_to_all`` exchanges overlapped with expert
    compute (``a2a_chunks``, ``None`` -> APEX_TPU_MOE_A2A_CHUNKS;
    ``1`` keeps the legacy un-overlapped single-shot exchange);
    ``axis_name=None`` runs all experts locally (the dense-equivalent
    used for parity tests).

    ``router`` may be top-1 (``(T,)`` index/gate) or top-k
    (``(k, T)``, e.g. :func:`top2_router`): the k choices share the
    capacity buffer with first choices taking priority (choice-major
    cumsum — the GShard Algorithm 1 slotting), and the combine sums the
    gate-weighted expert outputs per token.
    """
    T, H = x.shape
    idx = jnp.atleast_2d(router.expert_index)          # (k, T)
    gates = jnp.atleast_2d(router.gate)
    k = idx.shape[0]
    capacity = max(1, int(capacity_factor * k * T / num_experts))
    # gate == 0 marks a choice the router decided not to dispatch
    # (GShard second_policy="random"): it claims no capacity slot
    valid = gates.reshape(-1) > 0.0
    slot, keep = _dispatch_indices(idx.reshape(-1), num_experts,
                                   capacity,           # choice-major
                                   valid=valid)

    # scatter tokens into (num_experts, capacity, H); each of a token's
    # k choices occupies its own slot
    buf = jnp.zeros((num_experts, capacity, H), x.dtype)
    xk = jnp.broadcast_to(x[None], (k, T, H)).reshape(k * T, H)
    buf = buf.at[idx.reshape(-1), slot].add(
        jnp.where(keep[:, None], xk, 0))

    return _exchange_and_combine(
        buf, expert_fn, idx, gates, slot, keep, num_experts, capacity,
        axis_name, _resolve_chunks(a2a_chunks), x.dtype)


def _exchange_and_combine(buf, expert_fn, idx, gates, slot, keep,
                          num_experts, capacity, axis_name, chunks,
                          out_dtype) -> jnp.ndarray:
    """Shared exchange tail for the fused and unfused dispatch fronts:
    local (no collective), legacy single-shot, or the overlapped
    chunked schedule."""
    if axis_name is None:
        out = expert_fn(buf)
        return moe_combine(out, idx, slot, keep, gates,
                           out_dtype=out_dtype)

    n_shards = axis_size(axis_name)
    assert num_experts % n_shards == 0
    n = max(1, min(chunks, capacity))
    if n == 1:
        # the legacy un-overlapped trace, kept verbatim: the expert
        # matmul consumes the dispatch a2a's output as the immediately
        # next equation (zero slack — APX704's regression fixture)
        buf = jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                 concat_axis=1, tiled=True)
        out = expert_fn(buf)
        out = jax.lax.all_to_all(out, axis_name, split_axis=1,
                                 concat_axis=0, tiled=True)
        tok_out = out[idx.reshape(-1), slot]           # (k*T, H)
        gate = jnp.where(keep, gates.reshape(-1),
                         0.0).astype(jnp.float32)
        k, t = idx.shape
        combined = (tok_out.astype(jnp.float32) * gate[:, None]) \
            .reshape(k, t, -1).sum(0)
        return combined.astype(out_dtype)

    returns, cs = _chunked_expert_exchange(buf, expert_fn, axis_name,
                                           n)
    return _chunked_combine(returns, cs, idx, gates, slot, keep,
                            out_dtype)


def moe_dispatch_combine_fused(
        x: jnp.ndarray,
        logits: jnp.ndarray,
        expert_fn: Callable[[jnp.ndarray], jnp.ndarray],
        num_experts: int,
        capacity_factor: float = 1.25,
        axis_name: Optional[str] = EXPERT_AXIS,
        top_k: int = 1,
        second_policy: str = "all",
        rng: Optional[jax.Array] = None,
        a2a_chunks: Optional[int] = None,
        backend: Optional[str] = None
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The fused front end: router softmax, top-k select, capacity
    slotting and the buffer scatter ride ONE Pallas pass
    (:func:`apex_tpu.ops.moe_routing.moe_route_dispatch`, jnp twin off
    TPU) instead of four XLA stages, then the same overlapped exchange
    as :func:`moe_dispatch_combine`.  Routing decisions are
    bit-identical to the unfused path.  Returns ``(y, aux_loss)``."""
    T, _ = x.shape
    capacity = max(1, int(capacity_factor * top_k * T / num_experts))
    rd = moe_route_dispatch(x, logits, capacity=capacity, top_k=top_k,
                            second_policy=second_policy, rng=rng,
                            backend=backend)
    y = _exchange_and_combine(
        rd.buf, expert_fn, rd.expert_index, rd.gate, rd.slot, rd.keep,
        num_experts, capacity, axis_name, _resolve_chunks(a2a_chunks),
        x.dtype)
    return y, rd.load_balancing_loss


class ExpertParallelMLP:
    """Switch-style MoE FFN layer over an expert mesh axis.

    Functional container (params are an explicit pytree, like the other
    shard_map-mode layers):

    >>> layer = ExpertParallelMLP(hidden, ffn_hidden, num_experts)
    >>> params = layer.init(key)              # experts stacked on axis 0
    >>> y, aux = layer.apply(params, x)       # inside shard_map:
    ...                                       # params sharded P(EXPERT_AXIS)
    """

    def __init__(self, hidden_size: int, ffn_hidden_size: int,
                 num_experts: int, capacity_factor: float = 1.25,
                 axis_name: Optional[str] = EXPERT_AXIS,
                 router: str = "top1", second_policy: str = "all",
                 plan: Optional[MeshPlan] = None,
                 a2a_chunks: Optional[int] = None,
                 fused_dispatch: Optional[bool] = None):
        if router not in ("top1", "top2"):
            raise ValueError(f"router must be top1|top2, got {router!r}")
        if second_policy not in ("all", "random"):
            raise ValueError(f"second_policy must be 'all'|'random', "
                             f"got {second_policy!r}")
        if plan is not None:
            # topology as data: the plan's expert axis IS the axis name
            # (passing both only to disagree is a config bug)
            ep_axes = plan.axes_of_kind("expert")
            if len(ep_axes) != 1:
                raise ValueError(
                    f"plan {plan.describe()!r} must carry exactly one "
                    f"expert-kind axis to drive ExpertParallelMLP, "
                    f"got {[a.name for a in ep_axes]}")
            if axis_name not in (None, EXPERT_AXIS, ep_axes[0].name):
                raise ValueError(
                    f"plan names the expert axis "
                    f"{ep_axes[0].name!r} but axis_name="
                    f"{axis_name!r} was also given")
            axis_name = ep_axes[0].name
        self.hidden_size = hidden_size
        self.ffn_hidden_size = ffn_hidden_size
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.axis_name = axis_name
        self.router = router
        self.second_policy = second_policy
        # resolved at construction so the layer and its mesh_plan
        # price the SAME schedule (flags are ambient; plans are data)
        self.a2a_chunks = _resolve_chunks(a2a_chunks)
        self.fused_dispatch = (
            flag_bool("APEX_TPU_MOE_FUSED_DISPATCH")
            if fused_dispatch is None else bool(fused_dispatch))

    def mesh_plan(self, num_shards: int,
                  with_backward: bool = True) -> MeshPlan:
        """This layer's topology contract: experts sharded over one
        ``expert``-kind axis, router replicated, and the GShard
        dispatch algebra's collective budget — ``a2a_chunks``
        all_to_all each way under the overlapped schedule (their
        transposes double it when the layer trains).  The budget is a
        ceiling: at runtime the chunk count clamps to the capacity, so
        fewer collectives may execute.  The auditor checks a compiled
        entry against exactly this object; the runtime builds its
        shard_map specs from it.
        """
        if self.num_experts % num_shards != 0:
            raise ValueError(
                f"num_experts {self.num_experts} not divisible by "
                f"{num_shards} shards")
        ax = self.axis_name or EXPERT_AXIS
        per_direction = max(1, self.a2a_chunks)
        return MeshPlan.build(
            axes=((ax, num_shards, "expert"),),
            tensor_specs={
                # expert weights: stacked on dim 0, one slice per shard
                r"\['w[io]'\]": (ax,),
                # the router is the one intentionally-replicated param:
                # every shard routes its own tokens with the same table
                r"\['router'\]": (),
            },
            collective_budget={
                "all_to_all": 2 * per_direction
                * (2 if with_backward else 1)})

    def init(self, key: jax.Array) -> dict:
        kr, k1, k2 = jax.random.split(key, 3)
        e, h, f = self.num_experts, self.hidden_size, self.ffn_hidden_size
        s1 = (2.0 / h) ** 0.5
        return {
            "router": jax.random.normal(kr, (h, e), jnp.float32) * 0.02,
            "wi": jax.random.normal(k1, (e, h, f), jnp.float32) * s1,
            "wo": jax.random.normal(k2, (e, f, h), jnp.float32)
            * (2.0 / f) ** 0.5,
        }

    def apply(self, params: dict, x: jnp.ndarray, rng=None):
        """(T, H) -> ((T, H), aux_loss).  Inside shard_map, pass expert
        weights sharded ``P(EXPERT_AXIS)`` on their leading axis and the
        router replicated; tokens may be data-sharded on any other
        axis.  ``rng``: required when ``second_policy='random'`` (the
        GShard dispatch-saving Bernoulli draw)."""
        logits = x.astype(jnp.float32) @ params["router"]

        def expert_fn(buf):  # (local_e, rows, H)
            h = jnp.einsum("erh,ehf->erf", buf.astype(jnp.float32),
                           params["wi"])
            h = jax.nn.gelu(h)
            return jnp.einsum("erf,efh->erh", h,
                              params["wo"]).astype(buf.dtype)

        if self.fused_dispatch:
            return moe_dispatch_combine_fused(
                x, logits, expert_fn, self.num_experts,
                capacity_factor=self.capacity_factor,
                axis_name=self.axis_name,
                top_k=2 if self.router == "top2" else 1,
                second_policy=self.second_policy, rng=rng,
                a2a_chunks=self.a2a_chunks)

        router = (top2_router(logits,
                              second_policy=self.second_policy,
                              rng=rng)
                  if self.router == "top2" else top1_router(logits))
        y = moe_dispatch_combine(
            x, router, expert_fn, self.num_experts,
            capacity_factor=self.capacity_factor,
            axis_name=self.axis_name, a2a_chunks=self.a2a_chunks)
        return y, router.load_balancing_loss
