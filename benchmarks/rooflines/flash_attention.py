"""Operations and bytes of flash attention, from shapes alone.

One training step runs the forward and the backward kernel once per
layer.  Counted is what the algorithm needs, matmuls at 2 flops per
multiply-add:

* forward: S = Q K^T and O = P V -- 2 matmuls of ``b*h*s*s*d``;
* backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q -- 4
  matmuls.  The backward's recomputation of S (flash keeps no P) is
  the kernel's own choice and is NOT counted, as recomputed operations
  are not counted in an MFU;
* a causal mask halves every one of them (the upper triangle is never
  needed): ``s*s`` becomes ``s*(s+1)/2``.

Bytes are each operand read once and each result written once from
HBM: forward reads q, k, v and writes o and the row statistics
(float32 per row and head); backward reads q, k, v, o, dO and the
statistics and writes dq, dk, dv.  exp, max and sum are left out of the
flops (they are not MXU work; at d >= 64 they are under 5%)."""


def train_step(*, batch, seq, heads, head_dim, layers, causal,
               dtype_bytes=2):
    """(flops, bytes) of the attention kernels of one training step."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    matmul = 2 * batch * heads * pairs * head_dim
    flops = (2 + 4) * matmul
    tensor = batch * seq * heads * head_dim * dtype_bytes
    stats = batch * heads * seq * 4
    nbytes = (3 + 1) * tensor + stats + (5 + 3) * tensor + stats
    return layers * flops, layers * nbytes
