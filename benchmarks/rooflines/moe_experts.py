"""Operations and bytes of the routed experts of a dropless top-k
mixture, from what the engine counted over the decode ticks it ran.

A routed token (one row sent to one expert) costs the expert's three
matmuls, ``2 x hidden x width`` flops each: ``6 x width x hidden``.
The least a tick's expert layer has to read is the weights of the
**distinct experts that received a row** (``3 x hidden x width`` each),
not of all the experts there are -- a program that applies every expert
to every row reads more than this and stands below 100% for it, and a
later kernel that reads only the experts hit cannot read above 100% --
plus each row's normed input once and its output once.

``experts_hit`` (distinct experts that received a live row) and ``rows``
(live rows) are the engine's sums over the MoE layers and over the
decode ticks run while a profiler session was recording
(``ServingEngine.tick_sums``): the window a traced run's
trace covers, so ``per`` is ``trace``.  The other arguments are the
model's shapes.
"""


def ticks(*, experts_hit=0, rows=0, moe_layers, hidden, expert_width,
          experts_per_token, weight_bytes=2, in_bytes=2, out_bytes=4,
          **_others):
    """(flops, bytes) of the routed experts of those ticks.  ``rows``
    is counted once a tick, so it is multiplied by the MoE layers here;
    ``experts_hit`` is already summed over them."""
    routed = rows * moe_layers * experts_per_token
    flops = 6 * expert_width * hidden * routed
    nbytes = experts_hit * 3 * hidden * expert_width * weight_bytes \
        + rows * moe_layers * hidden * (in_bytes + out_bytes)
    return flops, nbytes
