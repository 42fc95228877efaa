"""Operations and bytes of paged (flash-)decode attention, from what
the host knows of the ticks it ran.

A decode tick attends one new query row per sequence to that
sequence's cached keys and values, in every layer.  The kernel has to
read the live pages (a page is read whole: ``block_size`` positions
whether filled or not), the query rows, and write the output rows;
each score costs ``2*d`` flops and each weighted value another
``2*d``.

``live_pages`` and ``rows`` are summed by the host over the ticks the
profiler saw; ``live_tokens`` likewise (positions actually filled)."""


def ticks(*, live_pages, live_tokens, rows, block_size, heads, head_dim,
          layers, kv_bytes=2, dtype_bytes=2):
    """(flops, bytes) of the decode-attention kernels of those ticks."""
    row = heads * head_dim
    nbytes = layers * (2 * live_pages * block_size * row * kv_bytes
                       + 2 * rows * row * dtype_bytes)
    flops = layers * 4 * live_tokens * row
    return flops, nbytes
