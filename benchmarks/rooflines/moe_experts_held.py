"""Operations and bytes of the routed experts of a layer that holds a
SHARE of its experts (one chip of an expert-parallel deployment), from
what the engine counted over the decode ticks it ran.

The layer routes every row over all the experts there are and computes
the (row, expert) pairs that fall on the experts it holds; the others
are other chips' work and are not counted here
(``rooflines/moe_experts.py`` prices ``rows x experts_per_token`` pairs,
which is right where every expert is held and sixteen times too many on
a chip that holds a sixteenth).  A pair that landed here costs the
expert's three matmuls, ``2 x hidden x width`` flops each.  The least
the layer has to read is the weights of the **distinct held experts
that received a row** (``3 x hidden x width`` each), plus each landed
pair's normed input once and its output once.

``experts_hit`` (distinct held experts that received a live row) and
``pairs_held`` (routed pairs of live rows that fell on held experts)
are the engine's sums over the MoE layers and over the decode ticks run
while a profiler session was recording (``ServingEngine.tick_sums``), so
``per`` is ``trace``.  The other arguments are the model's shapes.
"""


def ticks(*, experts_hit=0, pairs_held=0, hidden, expert_width,
          weight_bytes=2, in_bytes=2, out_bytes=4, **_others):
    """(flops, bytes) of the held routed experts of those ticks."""
    flops = 6 * expert_width * hidden * pairs_held
    nbytes = experts_hit * 3 * hidden * expert_width * weight_bytes \
        + pairs_held * hidden * (in_bytes + out_bytes)
    return flops, nbytes
