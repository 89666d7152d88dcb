"""What the benchmark makes from ``--seed`` (weights, records) and the
plain reference's three SGD steps that ``correct`` is decided against.
Imports nothing of the program.
"""

from __future__ import annotations

import concurrent.futures
from typing import Dict, List, Optional, Sequence

import numpy as np


def seed_words(seed: int, stream: int) -> List[int]:
    """Any whole-number seed (the driver's pass 2**31) as 32-bit words
    for numpy and JAX generators; ``stream`` separates weights, records
    and the step key."""
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), stream])
    return [int(w) for w in ss.generate_state(2)]


def make_weights(specs: Sequence[Dict], seed: int, gain: float = 2.0):
    """All parameters in ONE jitted call on the device, float32 as the
    program keeps its master weights: normal weights of variance
    ``gain / fan_in`` (2 is He et al.'s), small biases, scales near one."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = []
        for i, s in enumerate(specs):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, tuple(s["shape"]), jnp.float32)
            if s["kind"] == "weight":
                out.append(z * np.float32(np.sqrt(gain / s["fan_in"])))
            elif s["kind"] == "scale":
                out.append(1.0 + 0.1 * z)
            else:
                out.append(0.05 * z)
        return out

    key = jax.random.key(seed_words(seed, 1)[0] & 0x7FFFFFFF)
    return jax.jit(draw)(key)


def make_records(seed: int, n: int, image: Sequence[int], classes: int,
                 threads: int = 8):
    """``n`` records that all differ: unit-normal pixels around a mean
    that depends on the label, float32, drawn in parallel chunks whose
    generators are spawned from the seed (same seed, same bytes)."""
    rng = np.random.default_rng(seed_words(seed, 2))
    labels = rng.integers(0, classes, n).astype(np.int32)
    x = np.empty((n,) + tuple(image), np.float32)
    bounds = np.linspace(0, n, min(threads, n) + 1).astype(int)
    children = np.random.SeedSequence(seed_words(seed, 3)).spawn(
        len(bounds) - 1)

    def fill(i):
        lo, hi = bounds[i], bounds[i + 1]
        g = np.random.default_rng(children[i])
        g.standard_normal(out=x[lo:hi], dtype=np.float32)
        x[lo:hi] += (labels[lo:hi] / classes - 0.5).astype(
            np.float32)[:, None, None, None]

    with concurrent.futures.ThreadPoolExecutor(len(bounds) - 1) as pool:
        list(pool.map(fill, range(len(bounds) - 1)))
    return x, labels


def leaf_norms(tree: Sequence) -> np.ndarray:
    """Per-leaf Euclidean norms of host arrays, in float64."""
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64).ravel()))
                     for a in tree])


def follow(family, weights: Sequence, batches: Sequence, lr: float,
           momentum: float, quant: Optional[str] = None,
           devices: Optional[Sequence] = None) -> Dict:
    """The reference's first ``len(batches)`` steps of plain SGD with
    momentum (``v = mu v + g; w = w - lr v``, the first ``v`` being the
    first gradient) from ``weights``; ``batches`` are ``(x, y)`` host
    arrays.  Returns each step's mean loss, the per-leaf norms of the
    first gradient and of the parameters' change after the last step.

    A family whose rows do not couple (``BLOCK_ROWS``) is taken in blocks
    of rows, spread over ``devices`` when there are several; every
    whole-tree operation is one jitted program, so that a run loads a
    handful of programs from the cache instead of compiling hundreds of
    small ones."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = list(devices or jax.devices()[:1])
    rows = family.BLOCK_ROWS
    if rows is None:
        devices = devices[:1]
    mesh = Mesh(np.array(devices), ("rows",))
    by_rows, whole = NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())

    grad = jax.jit(jax.value_and_grad(
        lambda p, x, y: family.loss_sum(p, x, y, quant)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    mean = jax.jit(lambda g, n: [a / n for a in g])
    norms = jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(a))) for a in t]))
    @jax.jit
    def sgd(params, vel, g):
        """``vel=None`` is the first step: the velocity starts as ``g``."""
        vel = g if vel is None else [momentum * v + gi
                                     for v, gi in zip(vel, g)]
        return [p - lr * v for p, v in zip(params, vel)], vel

    minus = jax.jit(lambda a, b: [x - y for x, y in zip(a, b)])

    def mean_grad(params, x, y):
        block = (rows or len(x)) * len(devices)
        loss, g = 0.0, None
        for lo in range(0, len(x), block):
            xb = jax.device_put(x[lo:lo + block], by_rows)
            yb = jax.device_put(y[lo:lo + block], by_rows)
            part, gi = grad(params, xb, yb)
            loss += float(part)
            g = gi if g is None else add(g, gi)
        return loss / len(x), mean(g, np.float32(len(x)))

    w0 = [jax.device_put(np.asarray(w), whole) for w in weights]
    params, vel, losses, g1 = w0, None, [], None
    for x, y in batches:
        loss, g = mean_grad(params, x, y)
        losses.append(loss)
        if vel is None:
            g1 = np.asarray(norms(g), np.float64)
        params, vel = sgd(params, vel, g)
    delta = np.asarray(norms(minus(params, w0)), np.float64)
    return {"losses": losses, "grad1_norms": g1, "delta_norms": delta}
