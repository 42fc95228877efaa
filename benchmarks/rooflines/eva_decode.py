"""Operations and bytes of EVA's paged decode attention, from what the
engine counted over the decode ticks it ran.

A decode tick attends one new query row per sequence and layer to two
kinds of cached row under one softmax: the exact rows of the query's own
window up to itself, and one pooled row for every chunk of every earlier
window.  A pooled row has a cached row's shape, so a row read costs the
same whichever it is: its key and its value, ``heads x head_dim`` each
(at 32 heads of 128 in bf16: 16,384 bytes), and ``4 x heads x head_dim``
flops (a multiply-add a key element for the score, one a value element
for the output); the kernel also reads each sequence's queries and
writes its output (``heads x head_dim`` each).  Counted are the rows
read, never whole pages and never the uncompressed length: a kernel that
reads whole pages stands below 100% for the unfilled part of each row's
last window page.

``eva_window_rows`` and ``eva_summary_rows`` are the engine's sums
(``ServingEngine.tick_sums``: over the live rows, the layers and the
decode ticks run while a profiler session was recording -- the window a
traced run's trace covers, so ``per`` is ``trace``); ``rows`` is summed
once a tick and multiplied by ``layers`` here.  The other arguments are
the model's shapes.
"""


def ticks(*, eva_window_rows=0, eva_summary_rows=0, rows=0, layers, heads,
          head_dim, kv_bytes=2, dtype_bytes=2, **_others):
    """(flops, bytes) of the EVA decode kernels of those ticks."""
    read = eva_window_rows + eva_summary_rows
    flops = 4 * heads * head_dim * read
    nbytes = read * 2 * heads * head_dim * kv_bytes \
        + rows * layers * 2 * heads * head_dim * dtype_bytes
    return flops, nbytes
