"""Operations and bytes of EVA's prefill attention, from what the engine
counted over the prefill chunks it ran.

A prompt is prefilled a window a chunk.  A chunk of ``n`` bytes that
stands behind ``r`` pooled rows (one for every 16-byte chunk of the
windows before it) needs, a head and layer, ``n (n + 1) / 2`` (query,
key) pairs of its own causal triangle, counted ONCE, and ``n r`` pairs
with the pooled rows, which every query sees; a pair costs ``2 x
head_dim`` flops for the score and as many for the output.  Bytes are
the chunk's q, k and v read and o written once, and the pooled keys and
values read once.  Counted is what the algorithm needs of the real
bytes: a kernel that computes on a rung's padding, on the masked half of
a block on the diagonal or on the unfilled part of the pooled rows'
capacity stands below 100% for it.

``eva_chunk_pairs`` (the sum of ``n (n + 1) / 2 + n r``),
``eva_chunk_tokens`` (of ``n``) and ``eva_chunk_summary_rows`` (of
``r``) are the engine's sums (``ServingEngine.tick_sums``) over the
chunks of every rung run while a profiler session was recording, so
``per`` is ``trace`` and the metric reads the attention calls of every
chunk rung.  The other arguments are the model's shapes.
"""


def chunks(*, eva_chunk_pairs=0, eva_chunk_tokens=0,
           eva_chunk_summary_rows=0, layers, heads, head_dim,
           dtype_bytes=2, **_others):
    """(flops, bytes) of the attention calls of those chunks."""
    flops = layers * heads * eva_chunk_pairs * 4 * head_dim
    nbytes = layers * heads * head_dim * dtype_bytes \
        * (4 * eva_chunk_tokens + 2 * eva_chunk_summary_rows)
    return flops, nbytes
