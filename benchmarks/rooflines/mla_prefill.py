"""Operations and bytes of the prefill attention of latent attention
(MLA in its expanded form), from shapes alone.

A whole-prompt prefill runs the flash forward kernel once a layer over
the prompt padded to its rung: ``heads`` heads that score on ``qk_dim``
dims (128 without position + 64 rotary) and carry ``v_dim`` (128).
Counted is what the algorithm needs, matmuls at 2 flops per
multiply-add, the causal triangle ONCE: ``S = Q K^T`` costs ``2 x
qk_dim`` and ``O = P V`` ``2 x v_dim`` a (query, key) pair, and there are
``seq x (seq + 1) / 2`` pairs a head.  The published widths are counted
whatever the kernel computes: a kernel that pads a width, or computes
masked blocks of the square, stands below 100% for it.  Bytes are q and
k (``qk_dim`` wide) and v read once and o (``v_dim``) written once.

``seq`` is the rung the metric reads (``prefill_2048_ms.reason``: the
2,048 rung), so ``per`` is ``run``: one program run, times the runs in
the trace.  The other arguments are the model's shapes, as the builder
puts them beside the engine's sums.
"""


def rung(*, seq=2048, heads, qk_dim, v_dim, layers, dtype_bytes=2,
         **_others):
    """(flops, bytes) of the flash calls of one prefill at ``seq``."""
    pairs = seq * (seq + 1) // 2
    flops = layers * heads * pairs * 2 * (qk_dim + v_dim)
    nbytes = layers * heads * seq * 2 * (qk_dim + v_dim) * dtype_bytes
    return flops, nbytes
