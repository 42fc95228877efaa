"""Hardware perf check: per-tensor flash fwd+bwd at d=128, s=16384.

Round-5 recorded output on the v5e bench chip:
    wall slope: 45.6 ms -> 84.4 TF/s ; device: 39.5 ms -> 97.4 TF/s
(No cell of the benchmark runs this shape; PERF.md section 7.)
"""
import time, functools, jax, jax.numpy as jnp
from apex_tpu.ops.flash_attention import flash_attention
b, h, d, s = 1, 16, 128, 16384
q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d), jnp.bfloat16) * 0.5 for i in range(3))
def loss(q, k, v):
    o = flash_attention(q, k, v, causal=True)
    return jnp.sum(o.astype(jnp.float32) ** 2)
grad_fn = jax.grad(loss, argnums=(0, 1, 2))
def make_steps(n):
    @jax.jit
    def run(q, k, v):
        def body(c, _):
            q, k, v = c
            dq, dk, dv = grad_fn(q, k, v)
            eps = jnp.bfloat16(1e-6)
            return (q - eps*dq, k - eps*dk, v - eps*dv), ()
        return jax.lax.scan(body, (q, k, v), None, length=n)[0]
    return run
def force(o):
    float(jnp.sum(jnp.ravel(jax.tree_util.tree_leaves(o)[0])[:1]))
r1, r2 = make_steps(2), make_steps(8)
force(r1(q,k,v)); force(r2(q,k,v))
b1 = b2 = float("inf")
for _ in range(3):
    t0=time.perf_counter(); force(r1(q,k,v)); b1=min(b1,time.perf_counter()-t0)
    t0=time.perf_counter(); force(r2(q,k,v)); b2=min(b2,time.perf_counter()-t0)
flops = 7.0*b*h*s*s*d
dt = (b2-b1)/6
print(f"wall slope: {dt*1e3:.1f} ms -> {flops/dt/1e12:.1f} TF/s")
from apex_tpu.pyprof.measured import collect_device_ops
ops = collect_device_ops(lambda q,k,v: r1(q,k,v), q, k, v, iters=1)
dev = sum(o.total_us for o in ops)/2*1e-6
print(f"device: {dev*1e3:.1f} ms -> {flops/dev/1e12:.1f} TF/s")
