"""Operations and bytes of paged latent decode attention (MLA in its
absorbed form), from what the engine counted over the decode ticks it
ran.

A decode tick attends one new query row per sequence, ``heads`` absorbed
query heads of the latent's width, to that sequence's cached latents in
every layer.  A cached token is ONE row of ``latent_dim`` values for all
heads, read once: it is the key (all ``latent_dim`` values) and, in its
first ``value_dim``, the value.  So a live cached token costs
``latent_dim x kv_bytes`` bytes and ``2 x heads x (latent_dim +
value_dim)`` flops a layer (at 128 heads, 576 and 512: 1,152 bytes and
278,528 flops, 242 flops a byte, where a v5e's peaks cross at 240); the
kernel also reads each row's queries (``heads x latent_dim``) and writes
its output (``heads x value_dim``).  Counted are the positions filled,
not the pages that hold them: a kernel reads whole pages and stands
below 100% for the unfilled part of each row's last one.

``latent_tokens`` is the engine's sum (``ServingEngine.tick_sums``: live
positions, over the live rows, the latent layers and the decode ticks
run while a profiler session was recording -- the window a traced run's
trace covers, so ``per`` is ``trace``); ``rows`` is summed once a tick
and multiplied by ``layers`` here.  The other arguments are the model's
shapes.
"""


def ticks(*, latent_tokens=0, rows=0, layers, heads, latent_dim,
          value_dim, kv_bytes=2, dtype_bytes=2, **_others):
    """(flops, bytes) of the latent decode kernels of those ticks."""
    flops = 2 * heads * (latent_dim + value_dim) * latent_tokens
    nbytes = latent_tokens * latent_dim * kv_bytes \
        + rows * layers * heads * (latent_dim + value_dim) * dtype_bytes
    return flops, nbytes
