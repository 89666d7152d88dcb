"""What the ``granite_hybrid`` plan brought with it, in float32 on the
CPU: the four scalar multipliers of ``models.DecoderPlan`` (on the
embedding's output, the attention scores, what each part of a block adds
and the logits), each against the plain reference of
``benchmark/models/granite_hybrid.py`` and each doing nothing at its
default; the whole mixer (64 heads on one group) down to the scan's
``kernel/dispatch`` instant at the scan's own chunk; the tiny plan whole, loss and every gradient (the tied leaf's
two scaled parts among them); the counters and instants of a telemetry
run.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import decoder_cases
from bigdl_tpu import models
from bigdl_tpu.models.transformer import VocabHead
from bigdl_tpu.nn.module import functional_call, state_dict
from bigdl_tpu.ops import dispatch, ssd as scan
from decoder_cases import (check_loss_and_every_gradient, compiled,
                           train_through_local_optimizer)

tiny_conf = functools.partial(decoder_cases.tiny_conf, "granite_hybrid")


@pytest.fixture(scope="module")
def family():
    return decoder_cases.family("granite_hybrid")


def test_granite_hybrid_plan_loss_and_every_gradient_match_the_reference(
        family):
    """``build_decoder_lm`` on the tiny cut (published layers 1-4 of the
    toy ``layer_types``: mamba, mamba, attention, mamba; 160 positions,
    two chunks of 128, the second padded): the loss and every leaf's
    gradient on seeded weights, the one tied matrix's among them."""
    conf = tiny_conf()
    assert family.layers_of(conf) == ["ssm", "ssm", "full", "ssm"]
    own = list(state_dict(family.build(conf), kind="param"))
    # one [vocab, d] leaf, no head of its own; the multiplier holds none
    assert own[0] == "0.weight" and own[1] == "2.0.norm1.weight"
    assert own[-1] == "6.weight" and not [k for k in own if "proj" in k
                                         and k.startswith("7.")]
    check_loss_and_every_gradient(family, conf, 15)


#: one multiplier of the plan changed: the configuration key the
#: reference reads it from and the value the program is given instead
SCALARS = {"embedding": ("embedding_multiplier", 3.0),
           "attention": ("attention_multiplier", 2.0),
           "residual": ("residual_multiplier", 0.5),
           "logits": ("logits_scaling", 2.0)}


@pytest.mark.parametrize("key,changed", SCALARS.values(), ids=SCALARS)
def test_each_scalar_is_the_references(family, key, changed):
    """A two-layer plan (a mixer, the attention layer) whose program is
    built with ONE multiplier changed: its loss is the reference's at that
    value and not the reference's at the configuration's own."""
    from benchmark import reference

    # one chunk of 80 positions: the scalars are the point, not the scan
    conf = tiny_conf(first_layer=2, num_hidden_layers=2, sequence_length=80)
    assert family.layers_of(conf) == ["ssm", "full"]
    other = dict(conf, **{key: changed})
    specs = family.param_specs(other)
    weights = reference.make_weights(specs, 21, conf["init_gain"])
    x, y = family.make_records(21, 1, conf)
    model, crit = family.build(other), family.criterion()
    keys = list(state_dict(model, kind="param"))
    buffers = state_dict(model, kind="buffer")

    def system_loss(ws):
        out, _ = functional_call(model, {**dict(zip(keys, ws)), **buffers},
                                 jnp.asarray(x), training=True,
                                 rng=jax.random.key(0))
        return crit.update_output(out, jnp.asarray(y))

    got = float(compiled(system_loss, list(weights)))
    same, kept = (float(compiled(
        lambda ws, c=c: family.loss_sum(ws, jnp.asarray(x), jnp.asarray(y),
                                        conf=c), list(weights)))
        for c in (other, conf))
    assert abs(got - same) < 2e-5
    assert abs(got - kept) > 1e-3, (key, got, kept)


def _lowered(block, d=16):
    x = jax.ShapeDtypeStruct((1, 8, d), jnp.float32)
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         state_dict(block))
    text = jax.jit(lambda s, v: functional_call(block, s, v)[0]).lower(
        state, x).as_text()
    # locations differ by the line a module was built on, nothing else may
    return re.sub(r"loc\(.*?\)|#loc.*", "", text)


def test_a_scale_of_one_multiplies_nothing():
    """The defaults add no instruction: a block, an attention layer and a
    head built with their scale at its default lower to the text they had
    without the argument, and a scale that is not 1 adds the multiplies
    (one a part of the block)."""
    d = 16

    def block(**kw):
        return nn.DecoderBlock(d, nn.GroupedQueryAttention(d, 2, 1, 8),
                               nn.GatedMLP(d, 24), **kw)

    plain = _lowered(block())
    assert _lowered(block(residual_scale=1.0)) == plain
    scaled = _lowered(block(residual_scale=0.22))
    assert scaled.count("stablehlo.multiply") == \
        plain.count("stablehlo.multiply") + 2

    def attention(**kw):
        return nn.GroupedQueryAttention(d, 2, 1, 8, **kw)

    assert _lowered(attention(scale=None)) == _lowered(attention())
    assert _lowered(attention(scale=1 / 64)) != _lowered(attention())

    head = _lowered(VocabHead(d, 32))
    assert _lowered(VocabHead(d, 32, logit_scale=1.0)) == head
    assert _lowered(VocabHead(d, 32, logit_scale=8.0)).count(
        "stablehlo.divide") == head.count("stablehlo.divide") + 1

    plan = models.tiny_decoder_plan(64)
    assert (plan.embedding_scale, plan.attention_scale, plan.residual_scale,
            plan.logit_scale) == (1.0, None, 1.0, 1.0)
    # no multiplier module stands behind the embedding unless one is asked
    assert len(models.build_decoder_lm(plan, remat=False).layers) + 1 == len(
        models.build_decoder_lm(plan._replace(embedding_scale=12.0),
                                remat=False).layers)


def test_a_whole_mixer_reaches_the_scans_instant_at_the_scans_chunk():
    """A mixer of 64 heads on ONE group says so on the scan's instant, at
    ``ops.ssd.CHUNK``: the one place the chunk is set (a layer has no
    argument for it, as a plan has no field)."""
    x = jax.ShapeDtypeStruct((1, 300, 16), jnp.float32)

    def said(layer):
        dispatch.clear_decisions()
        state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype), state_dict(layer))
        jax.eval_shape(lambda s, v: functional_call(layer, s, v)[0],
                       state, x)
        (d,) = [d for d in dispatch.decisions() if d[0] == "ssd"]
        return d.launch

    whole = said(nn.Mamba2Mixer(16, 64, 2, 1, 8))
    assert whole == dict(chunk=scan.CHUNK, chunks=3, heads=64, head_dim=2,
                         state=8, groups=1)


def test_granite_hybrid_plan_trains_through_local_optimizer_and_is_traced(
        tmp_path, family):
    """The tiny cut through ``LocalOptimizer``: the loss falls, the run
    log carries the scan's ``kernel/dispatch`` instants at the scan's
    chunk on its one group, the attention layer's with its scale, and the
    ``ssm/*`` counters of every ``mamba`` layer and no other."""
    conf = tiny_conf()
    x, y = family.make_records(3, 8, conf)
    events, said = train_through_local_optimizer(
        family.build(conf), family.criterion(), zip(x, y), tmp_path, epochs=3)
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == 6 and steps[-1]["loss"] < steps[0]["loss"]
    legs = [e for e in events if e.get("name") == "kernel/dispatch"]
    scans = [e for e in legs if e["op"] == "ssd"]
    assert scans and {(e["backend"], e["reason"], e["chunk"], e["chunks"],
                       e["heads"], e["head_dim"], e["state"], e["groups"])
                      for e in scans} == {("xla", "unsupported-shape", 128, 2,
                                           4, 8, 16, 1)}
    attn = [e for e in legs if e["op"] == "attention"]
    assert {(e["q_heads"], e["kv_heads"], e["head_dim"], e["scale"])
            for e in attn} == {(4, 2, 16, 0.03125)}
    for name in ("ssm/decay_mean", "ssm/dt_mean", "ssm/state_norm_max"):
        seen = [e for e in events if e.get("name") == name]
        assert len(seen) == 6 * 3               # steps x mamba layers
        assert {e["layer"] for e in seen} == {"2.0.attn", "3.0.attn",
                                              "5.0.attn"}
        assert all(e["value"] > 0 for e in seen)
    assert len([m for m in said if "ssm/" in m]) == 3 * 3
    assert not np.isnan([e["loss"] for e in steps]).any()
