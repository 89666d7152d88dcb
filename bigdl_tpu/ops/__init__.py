"""bigdl_tpu.ops — functional TPU ops.

The reference keeps its perf-critical inner kernels in
``nn/NNPrimitive.scala`` (im2col/col2im/pooling hot loops) + MKL gemm; the
TPU-native analogue is (a) XLA itself for conv/matmul/elementwise fusion and
(b) three Pallas kernels, each timed in a benchmark cell: flash attention
(``attention.py``), the gated delta rule (``delta_rule.py``) and the
state-space scan (``ssd.py``).  Everything else here (``lrn.py``,
``norm.py``, ``pool.py``) is plain ``jnp`` under a ``jax.custom_vjp`` with
a hand-derived exact backward.
"""

from bigdl_tpu.ops.attention import (  # noqa: F401
    dot_product_attention,
    flash_attention,
    attention_partial,
    combine_partials,
)
from bigdl_tpu.ops.lrn import (  # noqa: F401
    cross_map_lrn,
    within_channel_lrn,
)
from bigdl_tpu.ops.norm import (  # noqa: F401
    contrastive_norm,
    divisive_norm,
    smooth2d,
    subtractive_norm,
)
from bigdl_tpu.ops.pool import (  # noqa: F401
    avg_pool,
    maxpool_tie_split,
)
