"""What the benchmark makes from ``--seed`` (weights, records) and the
plain reference's first steps that ``correct`` is decided against, under
the update rule the configuration names (``optimizers/``).  Imports
nothing of the program.
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


def host_weights(specs: Sequence[Dict], seed: int, gain: float = 2.0):
    """``make_weights`` brought to the host, for the reference: no copy
    stays on the device beside the one ``follow`` keeps."""
    return [np.asarray(a) for a in make_weights(specs, seed, gain)]


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


def make_token_records(seed: int, n: int, seq_len: int, vocab: int,
                       zipf: float = 0.0):
    """``n`` rows of ``seq_len + 1`` token ids below ``vocab``, int32, for
    a family's ``make_records``: ``x = ids[:, :-1]``, ``y = ids[:, 1:]``
    (the next token).  The token of rank ``r`` (from 0) is drawn with
    probability proportional to ``(r + 1) ** -zipf`` (``0`` is uniform),
    each position on its own, and rank is not id: a fixed permutation of
    the vocabulary, from the seed, says which id has which rank."""
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(zipf)
    cdf = np.cumsum(weights / weights.sum())
    draws = np.random.default_rng(seed_words(seed, 2)).random(
        (n, seq_len + 1))
    ranks = np.minimum(np.searchsorted(cdf, draws, side="right"), vocab - 1)
    id_of_rank = np.random.default_rng(seed_words(seed, 3)).permutation(
        vocab).astype(np.int32)
    ids = id_of_rank[ranks]
    return ids[:, :-1], ids[:, 1:]


def leaf_norms(tree: Sequence) -> np.ndarray:
    """Per-leaf Euclidean norms of host arrays, in float64."""
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64).ravel()))
                     for a in tree])


def follow(family, weights: Sequence, batches: Sequence, recipe, conf: Dict,
           quant: Optional[str] = None,
           devices: Optional[Sequence] = None) -> Dict:
    """The reference's first ``len(batches)`` steps from ``weights`` (host
    arrays) under the update rule of ``recipe`` (a file of
    ``optimizers/``, with the configuration's keys); ``batches`` are
    ``(x, y)`` host arrays whose rows are records.  Returns each step's
    mean loss, the per-leaf norms of the first gradient and of the
    parameters' change after the last step, and ``device_peak_bytes``:
    the most a device held between the reference's programs, live
    buffers and the temporaries reserved for loaded programs together (a
    process's own peak counters never fall, and the program set them).

    A family whose rows do not couple (``BLOCK_ROWS``) is taken in blocks
    of rows, spread over ``devices`` when there are several; every
    whole-tree operation is one jitted program, so that a run loads a
    handful of programs from the cache instead of compiling hundreds of
    small ones.

    On the device it keeps one copy of the parameters, the rule's state
    and one gradient (a second while blocks of rows add up), each
    updated in place: every program donates what it replaces.  The
    starting weights wait on the host and come back, for the change's
    norms, once the state and the gradient are gone."""
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
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    mean = jax.jit(lambda g, n: [a / n for a in g], donate_argnums=0)
    norms = jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(a))) for a in t]))
    update = jax.jit(recipe.update(conf), donate_argnums=(0, 1))
    minus = jax.jit(lambda a, b: [x - y for x, y in zip(a, b)],
                    donate_argnums=0)
    peak = 0

    def note_memory():
        nonlocal peak
        for d in devices:
            m = d.memory_stats() or {}
            peak = max(peak, m.get("bytes_in_use", 0)
                       + m.get("bytes_reserved", 0))

    def mean_grad(params, x, y):
        block = (rows or len(x)) * len(devices)
        loss, g = 0.0, None
        for lo in range(0, len(x), block):
            xb = jax.device_put(x[lo:lo + block], by_rows)
            yb = jax.device_put(y[lo:lo + block], by_rows)
            part, gi = grad(params, xb, yb)
            loss += float(part)
            note_memory()
            g = gi if g is None else add(g, gi)
        return loss / len(x), mean(g, np.float32(len(x)))

    w0 = [np.asarray(w) for w in weights]
    params = [jax.device_put(w, whole) for w in w0]
    state, losses, g1 = None, [], None
    for x, y in batches:
        loss, g = mean_grad(params, x, y)
        losses.append(loss)
        if g1 is None:
            g1 = np.asarray(norms(g), np.float64)
        params, state = update(params, state, g)
    del state, g
    delta = np.asarray(norms(minus(
        params, [jax.device_put(w, whole) for w in w0])), np.float64)
    note_memory()
    return {"losses": losses, "grad1_norms": g1, "delta_norms": delta,
            "device_peak_bytes": peak}
