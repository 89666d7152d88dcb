"""Adam (Kingma & Ba, arXiv:1412.6980, Algorithm 1) with bias correction,
as the program's ``optim.Adam`` has it:
``m = b1 m + (1-b1) g; v = b2 v + (1-b2) g^2;
w = w - lr (m / (1-b1^t)) / (sqrt(v / (1-b2^t)) + eps)``.
Keys: ``learning_rate``, ``beta1``, ``beta2``, ``epsilon``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def build(conf: Dict):
    import bigdl_tpu.optim as optim

    return optim.Adam(learning_rate=conf["learning_rate"],
                      beta1=conf["beta1"], beta2=conf["beta2"],
                      epsilon=conf["epsilon"])


def first_gradient(opt_state, conf: Dict):
    """After step 1 the first moment is ``(1 - beta1) g``."""
    import jax

    scale = np.float32(1.0 - conf["beta1"])
    return jax.tree.map(lambda m: np.asarray(m) / scale, opt_state["m"])


def update(conf: Dict):
    import jax.numpy as jnp

    lr, eps = conf["learning_rate"], conf["epsilon"]
    b1, b2 = conf["beta1"], conf["beta2"]

    def step(params, state, grads):
        if state is None:
            state = ([jnp.zeros_like(p) for p in params],
                     [jnp.zeros_like(p) for p in params],
                     jnp.zeros((), jnp.float32))
        m, v, t = state
        t = t + 1.0
        m = [b1 * a + (1 - b1) * g for a, g in zip(m, grads)]
        v = [b2 * a + (1 - b2) * g * g for a, g in zip(v, grads)]
        bc1, bc2 = 1.0 - jnp.power(b1, t), 1.0 - jnp.power(b2, t)
        params = [p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps)
                  for p, a, b in zip(params, m, v)]
        return params, (m, v, t)

    return step
