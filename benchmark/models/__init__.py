"""Families: one module each, found by the name a configuration gives
under ``"family"`` (a name with a dot in it is a module path of its own,
which is how a family that only the benchmark's tests use stays out of
this directory).  A family is the program's builder and the plain
float32 reference of the same mathematics, side by side.

A family module must define:

``build(conf)``
    the system under test, through the program's own builder.
``criterion()``
    the program's criterion, as ``cli train`` pairs it with the model.
``param_specs(conf)``
    the parameters in the program's own order: ``name``, ``shape``,
    ``kind`` (``weight`` with its ``fan_in``, ``scale`` or ``bias``);
    ``reference.make_weights`` draws them from the seed.
``loss_sum(params, x, y, quant=None)``
    plain ``jax.numpy``, importing nothing of the program: the SUM over
    the records (rows of ``x``) of what the program's criterion
    AVERAGES over a batch, so that blocks of rows add up and
    ``loss_sum / len(x)`` is the loss the program reports.  Where the
    criterion averages inside a record as well (a time-distributed
    criterion's mean over positions), that mean is the family's to
    express: the sum over records of each record's per-token mean.
    ``quant`` is the control's lower precision (``plain_ops.lower``).
``BLOCK_ROWS``
    rows per block of the reference's gradient, or ``None`` where a
    layer couples the rows of a batch (batch normalisation) and the
    batch is taken whole.

and may define:

``make_records(seed, n, conf) -> (x, y)``
    what a record is: host arrays whose rows are records, of any shape
    and dtype, made from the seed through ``reference.seed_words``
    streams 2 and 3 and from nothing else.  The harness looks no
    further inside them than ``len`` and row indexing.  A family
    without it gets unit-normal float32 images with one class label
    each, from the configuration's ``image`` and ``classes``.
``flops_per_record(conf)``
    the derivation behind the configuration's ``flops_per_record``.
"""

from __future__ import annotations

import importlib
from typing import Dict

from benchmark import reference


def load(conf: Dict):
    name = conf["family"]
    return importlib.import_module(
        name if "." in name else "benchmark.models." + name)


def make_records(family, seed: int, n: int, conf: Dict):
    """``n`` records from the seed, as the family defines a record."""
    make = getattr(family, "make_records", None)
    if make is not None:
        return make(seed, n, conf)
    return reference.make_records(seed, n, conf["image"], conf["classes"])
