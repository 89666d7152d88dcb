"""SGD with momentum as every entry point of the program trains with it
(``optim.SGD``, no dampening, no Nesterov, no weight decay):
``v = mu v + g; w = w - lr v``, the first ``v`` being the first gradient.
Keys: ``learning_rate``, ``momentum``.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def build(conf: Dict):
    import bigdl_tpu.optim as optim

    return optim.SGD(learning_rate=conf["learning_rate"],
                     momentum=conf["momentum"])


def first_gradient(opt_state, conf: Dict):
    """The velocity after step 1 is the first gradient."""
    import jax

    return jax.tree.map(np.asarray, opt_state["velocity"])


def update(conf: Dict):
    lr, momentum = conf["learning_rate"], conf["momentum"]

    def step(params, vel, grads):
        vel = grads if vel is None else [momentum * v + g
                                         for v, g in zip(vel, grads)]
        return [p - lr * v for p, v in zip(params, vel)], vel

    return step
