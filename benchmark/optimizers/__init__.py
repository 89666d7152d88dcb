"""Update rules, one recipe file each, found by the name a configuration
gives under ``"optimizer"``.  A recipe is everything the harness knows
about a rule; ``run.py``, ``reference.py`` and ``control.py`` hold none.

A recipe file defines:

``build(conf)``
    the program's ``OptimMethod`` for this rule, from the configuration's
    own keys (the only place a recipe imports the program).
``first_gradient(opt_state, conf)``
    the first gradient as the optimizer got it, read from the program's
    ``opt_state`` after step 1: a tree like the parameters, each leaf a
    host array (taken leaf by leaf, so nothing of the parameters' size
    is added on the device).
``update(conf)``
    the rule itself, plain float32 ``jax.numpy``, for the reference to
    follow: a function ``step(params, state, grads) -> (params, state)``
    over lists of leaves, where ``state=None`` is the state before the
    first step.  ``reference.follow`` jits it and donates the parameters
    and the state, so each is updated in place.
"""

from __future__ import annotations

import importlib
from typing import Dict


def load(conf: Dict):
    return importlib.import_module("benchmark.optimizers." + conf["optimizer"])
