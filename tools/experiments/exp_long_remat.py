"""A/B: remat on/off for the long-context LM (seq 4096, batch 4).

The transformer_lm_long bench config bakes remat=True (per-block
rematerialization), but with flash attention the activation memory is
O(S) — if the no-remat variant fits HBM at this shape, the ~22%
recompute tax measured at seq 1024 (`exp_remat`) is pure loss here.
Run on the chip; record the verdict in BASELINE.md and,
if no-remat wins AND fits, flip the config in bench.py.
"""
import sys, time, traceback
sys.path.insert(0, '/root/repo')
import jax, jax.numpy as jnp, numpy as np
import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import models
from bigdl_tpu.parallel.train_step import TrainStep
from bigdl_tpu.utils.rng import RNG

ITERS, SEQ, BATCH = 12, 4096, 4
rng = np.random.default_rng(0)


def run(tag, remat):
    RNG.set_seed(0)
    model = models.build_transformer_lm(
        32000, num_layers=6, embed_dim=512, num_heads=8, max_len=SEQ,
        remat=remat)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    step = TrainStep(model, crit,
                     optim.SGD(learning_rate=0.01, momentum=0.9),
                     compute_dtype=jnp.bfloat16)
    x = jnp.asarray(rng.integers(0, 32000, (BATCH, SEQ), dtype=np.int32))
    y = jnp.asarray(rng.integers(0, 32000, (BATCH, SEQ), dtype=np.int32))
    step.aot_scan(x, y, jax.random.key(0), ITERS)
    losses = step.run_scan(x, y, jax.random.key(1), ITERS)
    assert bool(jnp.isfinite(losses).all())
    float(jnp.sum(jax.tree_util.tree_leaves(step.params)[0]))
    t0 = time.perf_counter()
    step.run_scan(x, y, jax.random.key(2), ITERS)
    float(jnp.sum(jax.tree_util.tree_leaves(step.params)[0]))
    wall = time.perf_counter() - t0
    print(f"{tag}: {BATCH*ITERS/wall:,.1f} seq/s ({wall/ITERS*1e3:.1f} ms/step)",
          flush=True)


if __name__ == "__main__":
    run("remat", True)
    try:
        run("no-remat", False)
    except Exception:
        print("no-remat: FAILED (likely HBM OOM — remat stays)", flush=True)
        traceback.print_exc()
