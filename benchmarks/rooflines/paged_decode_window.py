"""Operations and bytes of paged decode attention with grouped query
heads, on full and on windowed layers, from what the engine counted
over the decode ticks it ran.

A decode tick attends one new query row per sequence, of ``full_heads``
or ``window_heads`` query heads, to that sequence's cached keys and
values of ``kv_heads`` heads, in every layer: all of them on a full
layer, the newest ``window`` on a windowed one.  The kernel has to read
the pages that hold those positions (a page is read whole,
``block_size`` positions of every cache head, filled or not), the query
rows, and write the output rows; each score costs ``2 x head_dim`` flops
a query head and each weighted value another ``2 x head_dim``.

``pages_full`` / ``tokens_full`` and ``pages_window`` / ``tokens_window``
are the engine's sums (``ServingEngine.tick_sums``: over the
live rows, over the layers of each kind, over the decode ticks run while
a profiler session was recording -- the window a traced run's trace
covers, so ``per`` is ``trace``); ``rows`` is summed once a tick.  The
other arguments are the model's shapes.
"""


def ticks(*, pages_full=0, pages_window=0, tokens_full=0, tokens_window=0,
          rows=0, full_layers, window_layers, full_heads, window_heads,
          kv_heads, head_dim, block_size, kv_bytes=2, dtype_bytes=2,
          **_others):
    """(flops, bytes) of the decode-attention kernels of those ticks."""
    page = block_size * kv_heads * head_dim * kv_bytes
    query_heads = full_layers * full_heads + window_layers * window_heads
    nbytes = 2 * (pages_full + pages_window) * page \
        + 2 * rows * query_heads * head_dim * dtype_bytes
    flops = 4 * head_dim * (tokens_full * full_heads
                            + tokens_window * window_heads)
    return flops, nbytes
