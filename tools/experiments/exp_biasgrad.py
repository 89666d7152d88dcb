"""Microbench: conv bias-grad reduce formulations at the profile's
hottest shape ([256,192,56,56] bf16 — the 3 ms/step backward fusion in
the round-5 Inception profile ran ~3.75x over its bandwidth bound).

Isolates the [C]-output reduce from the surrounding fusion so the
residual can be attributed: if (a) already hits the fused number, the
cost is the fusion's OTHER output; if (c) wins big, a custom bias-add
VJP routing the reduce through the MXU is worth landing.
"""
import sys, time
sys.path.insert(0, '/root/repo')
import jax, jax.numpy as jnp, numpy as np
from bigdl_tpu.utils.engine import enable_compile_cache
enable_compile_cache()

N, C, H, W = 256, 192, 56, 56
rng = np.random.default_rng(0)
gy = jnp.asarray(rng.normal(size=(N, C, H, W)).astype(np.float32),
                 dtype=jnp.bfloat16)


def timed(name, f):
    g = jax.jit(f)
    r = g(gy); jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(20):
        r = g(gy)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / 20
    gb = N * C * H * W * 2 / 1e9
    print(f"{name}: {dt*1e3:.3f} ms ({gb/dt:.0f} GB/s effective)",
          flush=True)


timed("a) bf16 sum((0,2,3))", lambda g: g.sum((0, 2, 3)))
timed("b) f32-accum sum", lambda g: g.astype(jnp.float32).sum((0, 2, 3))
      .astype(jnp.bfloat16))
timed("c) MXU ones-einsum", lambda g: jnp.einsum(
    "nchw,n->ch", g, jnp.ones((N,), jnp.bfloat16),
    preferred_element_type=jnp.float32).sum((1,)).astype(jnp.bfloat16))
timed("d) reshape 2d sum", lambda g: g.transpose(1, 0, 2, 3)
      .reshape(C, -1).sum(1))
