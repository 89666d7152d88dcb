"""The Mamba-2 state-space mixer and what the ``nemotron_h`` plan brought
with it, in float32 on the CPU: ``ops.ssd.ssd`` against its token-by-token
definition (values, the last state, every gradient; decays near 1 and
near 0; a carry zeroed on purpose is seen); ``nn.Mamba2Mixer``, the
gate-first grouped ``nn.GatedRMSNorm``, the latent ``nn.RoutedExperts``
on its three paths and the one-part ``nn.DecoderBlock`` against the plain
reference of ``benchmark/models/nemotron_h.py``; the share tests (four
head shares of a mixer and of the attention layer, eight expert shares of
a latent layer, add up to the uncut layer); the configuration's counts;
the tiny plan whole; the counters and instants of a telemetry run.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import decoder_cases
from bigdl_tpu import models
from bigdl_tpu.nn.module import functional_call, load_state_dict, state_dict
from bigdl_tpu.ops import dispatch, ssd as scan
from decoder_cases import (call, check_loss_and_every_gradient, compiled,
                           draw, train_through_local_optimizer)

tiny_conf = functools.partial(decoder_cases.tiny_conf, "nemotron_h")


@pytest.fixture(scope="module")
def family():
    return decoder_cases.family("nemotron_h")


# -- the scan ---------------------------------------------------------------------

def _scan_inputs(seed, dt_shift, b=1, s=40, h=4, p=8, g=2, n=8):
    """``dt_shift`` moves the steps before their softplus: far below 0 the
    decays are near 1 (a state that outlives many chunks), far above near
    0 (every head forgets at once)."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f(b, s, h) + dt_shift)
    return (f(b, s, h, p), dt, -jnp.exp(0.05 * f(h)), f(b, s, g, n),
            f(b, s, g, n), 1.0 + 0.1 * f(h))


#: chunk, shift of ``dt``, and the shapes where they are not
#: ``_scan_inputs``'s own; the last two are a whole mixer, 64 heads on ONE
#: group, at the chunk the layers run (``CHUNK``: 300 tokens are three
#: chunks, the last padded) and at the 256 a model publishes for its own
#: kernels (two chunks); decays near 1, so a later chunk's output is
#: mostly the state the earlier ones left
SCANS = {"two-chunks-decays-near-one": (20, -4.0, {}),
         "three-chunks-padded-decays-near-zero": (16, 4.0, {}),
         "three-chunks-padded-decays-spread": (16, 0.0, {}),
         "chunk-128-64-heads-one-group": (scan.CHUNK, -4.0, dict(
             s=300, h=64, p=4, g=1, n=8)),
         "chunk-256-64-heads-one-group": (256, -4.0, dict(
             s=300, h=64, p=4, g=1, n=8))}


@pytest.mark.parametrize("chunk,dt_shift,shape", SCANS.values(), ids=SCANS)
def test_chunked_scan_is_the_token_by_token_recurrence(chunk, dt_shift,
                                                       shape):
    """Values, the state after the last token, and the gradient by every
    input, at a length the chunk divides and at one it does not."""
    args = _scan_inputs(1, dt_shift, **shape)
    weigh = jnp.asarray(np.random.default_rng(2).standard_normal(
        args[0].shape), jnp.float32)

    def both(fn):
        def loss(*a):
            y, state = fn(*a, return_state=True)
            return jnp.sum(y * weigh) + jnp.sum(state), (y, state)
        return compiled(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                           has_aux=True), *args)

    (_, (y, state)), grads = both(functools.partial(scan.ssd, chunk=chunk))
    (_, (y0, state0)), grads0 = both(scan.ssd_recurrent)
    decay = np.exp(np.asarray(args[1]) * np.asarray(args[2]))
    assert (decay.mean() > 0.95) if dt_shift < -1 else (
        (decay.mean() < 0.05) if dt_shift > 1 else True)
    np.testing.assert_allclose(y, y0, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(state, state0, rtol=2e-4, atol=2e-5)
    for got, want in zip(grads, grads0):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4 * float(
            np.abs(want).max()))


#: a shape the Pallas leg takes: the cells' heads of 64 and state of 128
KERNEL_SHAPE = dict(p=64, n=128)


@pytest.mark.parametrize("leg", ["xla", "pallas"])
def test_a_carry_zeroed_on_purpose_is_seen(leg, monkeypatch):
    """With decays near 1 most of an output comes from earlier chunks: a
    scan that forgets the state between chunks is far from the
    recurrence, so the comparison above would refuse it.  On both legs:
    the XLA leg's carry is ``_carry``, the Pallas leg's the ``_advance``
    its kernels step the state in VMEM by."""
    monkeypatch.setenv("BIGDL_KERNELS", leg)
    chunk, shape = (8, {}) if leg == "xla" else (
        scan.CHUNK, dict(s=384, h=2, g=1, **KERNEL_SHAPE))
    args = _scan_inputs(3, -4.0, **shape)
    want = compiled(scan.ssd_recurrent, *args)
    dispatch.clear_decisions()
    good = compiled(functools.partial(scan.ssd, chunk=chunk), *args)
    assert [d[1] for d in dispatch.decisions() if d[0] == "ssd"] == [leg]
    np.testing.assert_allclose(good, want, rtol=2e-4, atol=2e-5)

    real = scan._carry

    def forgetful(decay, own):
        state, entering = real(decay, own)
        return state, jnp.zeros_like(entering)

    monkeypatch.setattr(scan, "_carry", forgetful)
    monkeypatch.setattr(scan, "_advance",
                        lambda state, decay, own: jnp.zeros_like(own))
    # a kernel is traced once a shape: not the sound one again, and the
    # forgetful one for nobody else
    monkeypatch.setattr(scan, "_launch", functools.lru_cache(maxsize=None)(
        scan._Launch))
    bad = compiled(functools.partial(scan.ssd, chunk=chunk), *args)
    gap = np.abs(np.asarray(bad) - np.asarray(want)).max()
    assert gap > 0.1 * np.abs(np.asarray(want)).max()


#: dtype, what it is compared with, shift of ``dt``, the kernel's head
#: block (None: its own), shape, and the weight of ``y`` in the loss (1
#: where a case names none).  float32 against the definition, bfloat16
#: against the XLA leg (the same roundings in the same places); one group
#: and two; 300 tokens are three chunks, the last padded; heads a group
#: over TWO grid steps, so that ``dB``, ``dC``, ``d dt``, ``dA`` and ``dD``
#: are summed over blocks.  From "two-records-of-three-chunks" on, what
#: only a state carried in VMEM can get wrong: a second record has to
#: start from zero and from ITS last state's cotangent (decays near 1, so
#: what the first record left would be seen); two blocks of heads over
#: five and six chunks keep a state and a cotangent each, walked forward
#: and back; with ``y`` out of the loss every gradient comes through the
#: last state's cotangent and the carry's backward alone
KERNEL_SCANS = {
    "float32-one-group-padded": (
        jnp.float32, "recurrent", -4.0, None, dict(s=300, h=2, g=1)),
    "float32-two-groups-two-records": (
        jnp.float32, "recurrent", 0.0, None, dict(b=2, s=256, h=4, g=2)),
    "float32-two-blocks-a-group": (
        jnp.float32, "recurrent", -2.0, 2, dict(s=256, h=4, g=1)),
    "bfloat16-two-groups": (
        jnp.bfloat16, "xla", -2.0, None, dict(s=384, h=4, g=2)),
    "bfloat16-one-group-padded": (
        jnp.bfloat16, "xla", -4.0, None, dict(s=300, h=4, g=1)),
    "float32-two-records-of-three-chunks-decays-near-one": (
        jnp.float32, "recurrent", -4.0, None, dict(b=2, s=384, h=2, g=1)),
    "float32-five-chunks-two-blocks-a-group": (
        jnp.float32, "recurrent", -3.0, 2, dict(s=640, h=4, g=1)),
    "float32-last-state-alone-in-the-loss": (
        jnp.float32, "recurrent", -3.0, 2, dict(b=2, s=384, h=4, g=2), 0.0),
    "bfloat16-two-records-six-chunks-two-blocks-a-group": (
        jnp.bfloat16, "xla", -4.0, 2, dict(b=2, s=700, h=4, g=1))}


@pytest.mark.parametrize(
    "dtype,against,dt_shift,head_block,shape,y_weight",
    [(case + (1.0,))[:6] for case in KERNEL_SCANS.values()],
    ids=KERNEL_SCANS)
def test_pallas_leg_is_the_scan(dtype, against, dt_shift, head_block, shape,
                                y_weight, monkeypatch):
    """The interpreted kernels, the state carried in VMEM from chunk to
    chunk: values, the state after the last token and the gradient by
    ``x``, ``dt``, ``A``, ``B``, ``C`` and ``D``."""
    if head_block:
        monkeypatch.setattr(scan, "HEAD_BLOCK", head_block)
    args = _scan_inputs(5, dt_shift, **shape, **KERNEL_SHAPE)
    args = tuple(a.astype(dtype) if i in (0, 3, 4) else a
                 for i, a in enumerate(args))
    rng = np.random.default_rng(6)
    weigh = y_weight * jnp.asarray(rng.standard_normal(args[0].shape),
                                   jnp.float32)
    heads, groups = shape["h"], shape["g"]
    weigh_state = jnp.asarray(rng.standard_normal(
        (shape.get("b", 1), heads, KERNEL_SHAPE["p"], KERNEL_SHAPE["n"])),
        jnp.float32)

    def both(fn, leg):
        def loss(*a):
            y, state = fn(*a, return_state=True)
            return (jnp.sum(y.astype(jnp.float32) * weigh)
                    + jnp.sum(state * weigh_state), (y, state))
        monkeypatch.setenv("BIGDL_KERNELS", leg)
        dispatch.clear_decisions()
        out = compiled(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                          has_aux=True), *args)
        return out, [d for d in dispatch.decisions() if d[0] == "ssd"]

    ((_, (y, state)), grads), (said,) = both(scan.ssd, "pallas")
    assert tuple(said) == ("ssd", "pallas", "forced:BIGDL_KERNELS=pallas")
    block = head_block or min(heads // groups, scan.HEAD_BLOCK)
    assert said.launch["head_block"] == block
    assert said.launch["grid"] == (shape.get("b", 1), -(-shape["s"] // 128),
                                   heads // block)
    ((_, (y0, state0)), grads0), _ = both(
        scan.ssd_recurrent if against == "recurrent" else
        functools.partial(scan.ssd, chunk=scan.CHUNK), "xla")
    assert y.dtype == dtype and state.dtype == jnp.float32
    # bfloat16: a rounding of y is 2^-8 of it, and the legs round the same
    # operands, not bit for bit the same sums
    rtol = 2e-4 if dtype == jnp.float32 else 2e-2
    close = functools.partial(np.testing.assert_allclose, rtol=rtol)
    f32 = functools.partial(np.asarray, dtype=np.float32)
    close(f32(y), f32(y0), atol=rtol * 0.1 * float(np.abs(f32(y0)).max()))
    close(state, state0, atol=rtol * 0.1 * float(np.abs(state0).max()))
    for got, want in zip(grads, grads0):
        close(f32(got), f32(want),
              atol=(2e-4 if dtype == jnp.float32 else 2e-2)
              * float(np.abs(f32(want)).max()))


def test_the_scan_announces_the_chosen_leg_and_refuses_odd_groups(
        monkeypatch):
    """Off the TPU ``auto`` keeps the XLA leg; ``pallas`` takes the kernels
    where the shape allows (chunk 128, a head of whole sublane tiles, a
    state of whole lane tiles) and says how they are launched."""
    def said_for(*shapes):
        dispatch.clear_decisions()
        # a function of its own a call: a trace that is found again in the
        # cache announces nothing, and the knob is read at trace time
        out = jax.eval_shape(lambda *a: scan.ssd(*a), *(
            jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes))
        (said,) = [d for d in dispatch.decisions() if d[0] == "ssd"]
        return out, said

    small = ((2, 300, 6, 8), (2, 300, 6), (6,), (2, 300, 3, 16),
             (2, 300, 3, 16), (6,))
    wide = ((2, 300, 12, 64), (2, 300, 12), (12,), (2, 300, 2, 128),
            (2, 300, 2, 128), (12,))
    facts = dict(chunk=128, chunks=3, heads=6, head_dim=8, state=16, groups=3)
    monkeypatch.delenv("BIGDL_KERNELS", raising=False)
    out, said = said_for(*small)
    assert out.shape == (2, 300, 6, 8)
    assert tuple(said) == ("ssd", "xla", "unsupported-shape")
    assert said.launch == facts
    _, said = said_for(*wide)
    assert tuple(said) == ("ssd", "xla", "auto:off-tpu")
    wide_facts = dict(facts, heads=12, head_dim=64, state=128, groups=2)
    assert said.launch == wide_facts
    monkeypatch.setenv("BIGDL_KERNELS", "pallas")
    _, said = said_for(*small)
    assert tuple(said) == ("ssd", "xla", "unsupported-shape")
    out, said = said_for(*wide)
    assert out.shape == (2, 300, 12, 64)
    assert tuple(said) == ("ssd", "pallas", "forced:BIGDL_KERNELS=pallas")
    assert said.launch == dict(wide_facts, head_block=6, grid=(2, 3, 2),
                               carry="vmem", calls=2,
                               state_bytes=12 * 64 * 128 * 4)
    # a record's states are carried in VMEM: more of them than their
    # budget there go the other way
    assert 64 * 64 * 128 * 4 * 2 == scan.STATE_BUDGET
    for heads, leg in ((128, "pallas"), (144, "xla")):
        big = tuple((2, 300, heads) + s[3:] if len(s) > 2 and s[2] == 12
                    else (heads,) if s == (12,) else s for s in wide)
        _, said = said_for(*big)
        assert said[1] == leg and said.launch["heads"] == heads
    # a state of half a lane tile; a short sequence is one chunk of its
    # own length
    narrow = wide[:3] + ((2, 300, 2, 64),) * 2 + wide[5:]
    assert tuple(said_for(*narrow)[1]) == ("ssd", "xla", "unsupported-shape")
    short = tuple((2, 100) + s[2:] if len(s) > 1 else s for s in wide)
    assert tuple(said_for(*short)[1]) == ("ssd", "xla", "unsupported-shape")
    monkeypatch.setenv("BIGDL_KERNELS", "xla")
    assert tuple(said_for(*wide)[1]) == ("ssd", "xla",
                                         "forced:BIGDL_KERNELS=xla")
    assert scan.CHUNK == 128
    with pytest.raises(ValueError, match="6 heads over 4 groups"):
        jax.eval_shape(scan.ssd, *(
            jax.ShapeDtypeStruct(s, jnp.float32) for s in (
                small[:3] + ((2, 300, 4, 16),) * 2 + small[5:])))


# -- the mixer and its norm -----------------------------------------------------------

MIXER_LEAVES = ("conv_weight", "conv_bias", "A_log", "D", "dt_bias",
                "in_proj.weight", "norm.weight", "out_proj.weight")


def _mixer_weights(seed, d, heads, p, groups, state, taps=4):
    rng = np.random.default_rng(seed)
    inner, bc = heads * p, groups * state
    return [draw(rng, inner + 2 * bc, taps, fan_in=taps),
            jnp.asarray(0.05 * rng.standard_normal(inner + 2 * bc),
                        jnp.float32),
            jnp.asarray(0.05 * rng.standard_normal(heads), jnp.float32),
            jnp.asarray(1 + 0.1 * rng.standard_normal(heads), jnp.float32),
            jnp.asarray(2.0 * rng.standard_normal(heads), jnp.float32),
            draw(rng, 2 * inner + 2 * bc + heads, d, fan_in=d),
            jnp.asarray(1 + 0.1 * rng.standard_normal(inner), jnp.float32),
            draw(rng, d, inner, fan_in=inner)]


def _mixer_conf(heads, p, groups, state):
    return dict(mamba_num_heads=heads, mamba_head_dim=p, n_groups=groups,
                ssm_state_size=state, norm_eps=1e-5)


def test_mamba2_mixer_is_the_reference_layer(family):
    """Values and the gradient of every parameter and of the input, two
    records of 150 positions (two chunks, the second padded), against the
    reference's token-by-token equations a record."""
    d, sizes = 32, (4, 8, 2, 16)
    layer = nn.Mamba2Mixer(d, *sizes)
    assert tuple(state_dict(layer, kind="param")) == MIXER_LEAVES
    weights = _mixer_weights(4, d, *sizes)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.standard_normal((2, 150, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((2, 150, d)), jnp.float32)
    buffers, conf = state_dict(layer, kind="buffer"), _mixer_conf(*sizes)

    def got_fn(ws, x):
        out, _ = functional_call(
            layer, {**dict(zip(MIXER_LEAVES, ws)), **buffers}, x)
        return jnp.sum(out * do)

    def want_fn(ws, x):
        each = jax.lax.map(lambda r: jnp.sum(
            family.mamba_mixer(r[0], ws, conf) * r[1]), (x, do))
        return jnp.sum(each)

    got = compiled(jax.value_and_grad(got_fn, argnums=(0, 1)), weights, u)
    want = compiled(jax.value_and_grad(want_fn, argnums=(0, 1)), weights, u)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=2e-4 * float(np.abs(b).max()))
    # a later token moves no earlier output and no other record, and the
    # buffer holds the decays' mean, the steps' mean and a state's norm
    base, state = call(layer, u)
    moved, _ = call(layer, u.at[0, 130].add(1.0))
    np.testing.assert_array_equal(moved[0, :130], base[0, :130])
    np.testing.assert_array_equal(moved[1], base[1])
    assert np.abs(np.asarray(moved[0, 130:] - base[0, 130:])).max() > 0
    decay, dt, norm = np.asarray(state["ssm_stats"])
    assert 0 < decay < 1 and dt > 0 and norm > 0


NORMS = {"gate-first-in-groups": dict(gate_first=True, group_size=8),
         "gate-first": dict(gate_first=True),
         "norm-first-in-groups": dict(group_size=8)}


@pytest.mark.parametrize("how", NORMS.values(), ids=NORMS)
def test_gated_norm_orders_and_groups(how):
    rng = np.random.default_rng(6)
    x, z = (jnp.asarray(rng.standard_normal((3, 5, 16)), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(1 + 0.1 * rng.standard_normal(16), jnp.float32)
    layer = nn.GatedRMSNorm(16, 1e-5, **how)
    load_state_dict(layer, {"weight": w})
    got, _ = call(layer, (x, z))
    x, z, w = (np.asarray(a, np.float64) for a in (x, z, w))
    gate = z / (1 + np.exp(-z))

    def norm(y):
        size = how.get("group_size") or 16
        groups = y.reshape(3, 5, 16 // size, size)
        groups = groups / np.sqrt((groups ** 2).mean(-1, keepdims=True) + 1e-5)
        return groups.reshape(3, 5, 16)

    want = w * norm(x * gate) if how.get("gate_first") else w * norm(x) * gate
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="groups of 5 channels over 16"):
        nn.GatedRMSNorm(16, group_size=5)


# -- the latent expert layer --------------------------------------------------------------

LATENT_LEAVES = ("experts_up", "experts_down", "select_bias", "router.weight",
                 "latent_in.weight", "latent_out.weight",
                 "shared.up_proj.weight", "shared.down_proj.weight")


def _latent_conf(n, k):
    return dict(num_experts_per_tok=k, norm_topk_prob=True,
                routed_scaling_factor=5, held_experts=[0, n])


def _latent_weights(seed, d, latent, width, shared, n):
    rng = np.random.default_rng(seed)
    return [draw(rng, n, latent, width, fan_in=latent),
            draw(rng, n, width, latent, fan_in=width),
            jnp.asarray(0.05 * rng.standard_normal(n), jnp.float32),
            draw(rng, n, d, fan_in=d), draw(rng, latent, d, fan_in=d),
            draw(rng, d, latent, fan_in=latent),
            draw(rng, shared, d, fan_in=d), draw(rng, d, shared, fan_in=shared)]


def _latent_layer(weights, d, latent, width, shared, n, k, held):
    first, count = held
    layer = nn.RoutedExperts(
        d, width, n, k, held=held, shared_width=shared, routed_scale=5.0,
        score="sigmoid", select_bias=True, activation="relu2",
        latent=latent)
    own = [w[first:first + count] for w in weights[:2]] + list(weights[2:])
    leaves = LATENT_LEAVES if shared else LATENT_LEAVES[:6]
    load_state_dict(layer, dict(zip(leaves, own)), strict=False)
    return layer


#: held experts, capacity factor, (combine the instant names, rows that
#: took the exact path)
PATHS = {"grouped-prefix": ((0, 4), 2.0, "scatter_add", False),
         "exact-under-the-cond": ((0, 4), 0.25, "scatter_add", True),
         "fold-whole-order": ((0, 16), 4.0, "fold", False)}


@pytest.mark.parametrize("held,factor,combine,spills", PATHS.values(),
                         ids=PATHS)
def test_latent_routed_experts_match_the_reference_on_every_path(
        held, factor, combine, spills, family, monkeypatch):
    """Ungated squared-ReLU experts in a latent narrower than the model,
    the shared expert on the model's width: values and the gradient of
    every leaf on the prefix path, on the exact path (a capacity too small
    on purpose) and on the whole-order fold."""
    d, latent, width, shared, n, k, t = 32, 16, 24, 40, 16, 4, 48
    monkeypatch.setattr(nn.RoutedExperts, "CAPACITY_FACTOR", factor)
    weights = _latent_weights(7, d, latent, width, shared, n)
    layer = _latent_layer(weights, d, latent, width, shared, n, k, held)
    assert tuple(state_dict(layer, kind="param")) == LATENT_LEAVES
    first, count = held
    own = [w[first:first + count] for w in weights[:2]] + weights[2:]
    rng = np.random.default_rng(8)
    u = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    buffers, conf = state_dict(layer, kind="buffer"), _latent_conf(n, k)

    def got_fn(ws):
        out, state = functional_call(
            layer, {**dict(zip(LATENT_LEAVES, ws)), **buffers}, u)
        return jnp.sum(out * do), state["held_load"]

    def want_fn(ws):
        return jnp.sum(family.latent_experts(u, ws, conf, held=held) * do)

    (got, load), got_grads = compiled(
        jax.value_and_grad(got_fn, has_aux=True), own)
    want, want_grads = compiled(jax.value_and_grad(want_fn), own)
    assert bool(np.asarray(load)[-1] > 0) == spills
    assert layer.capacity(t) < t * k or combine == "fold"
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for name, a, b in zip(LATENT_LEAVES, got_grads, want_grads):
        if name == "select_bias":
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=1e-3 * float(np.abs(b).max()))


def test_the_layer_says_what_it_is_and_refuses_what_it_is_not():
    layer = nn.RoutedExperts(32, 24, 16, 4, held=(0, 4), shared_width=40,
                             activation="relu2", latent=16)
    own = state_dict(layer, kind="param")
    assert "experts_gate" not in own
    assert own["experts_up"].shape == (4, 16, 24)
    assert own["experts_down"].shape == (4, 24, 16)
    assert "latent=16" in repr(layer) and "activation=relu2" in repr(layer)
    assert isinstance(layer.shared, nn.FeedForward)
    gated = nn.RoutedExperts(32, 24, 16, 4, held=(0, 4), shared_width=40)
    assert list(state_dict(gated, kind="param"))[:3] == [
        "experts_gate", "experts_up", "experts_down"]
    assert "latent=None" in repr(gated) and "activation=silu" in repr(gated)
    assert isinstance(gated.shared, nn.GatedMLP)
    assert gated.gated and not layer.gated
    with pytest.raises(ValueError, match="'gelu'.*silu, relu2"):
        nn.RoutedExperts(32, 24, 16, 4, activation="gelu")


# -- the one-part block ---------------------------------------------------------------------

def test_a_block_of_one_part_is_one_norm_and_one_add(family):
    d = 16
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 6, d)), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.standard_normal(d), jnp.float32)
    up, down = draw(rng, 24, d, fan_in=d), draw(rng, d, 24, fan_in=24)
    ffn_only = nn.DecoderBlock(d, None, nn.FeedForward(d, 24, "relu2"),
                               eps=1e-5)
    assert list(state_dict(ffn_only, kind="param")) == [
        "norm2.weight", "ffn.up_proj.weight", "ffn.down_proj.weight"]
    load_state_dict(ffn_only, {"norm2.weight": scale,
                               "ffn.up_proj.weight": up,
                               "ffn.down_proj.weight": down})
    got, _ = call(ffn_only, x)
    u = x * scale / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    with jax.default_matmul_precision("highest"):
        want = x + family.shared_expert(u.reshape(12, d), (up, down)).reshape(
            2, 6, d)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    mixer_only = nn.DecoderBlock(d, nn.Mamba2Mixer(d, 2, 4, 1, 8), None)
    names = list(state_dict(mixer_only, kind="param"))
    assert names[0] == "norm1.weight" and not [k for k in names if "2" in k]
    assert all(k.startswith("attn.") for k in names[1:])
    both = nn.DecoderBlock(d, nn.Mamba2Mixer(d, 2, 4, 1, 8),
                           nn.GatedMLP(d, 24))
    assert [k for k in state_dict(both, kind="param") if "norm" in k
            and "attn" not in k] == ["norm1.weight", "norm2.weight"]
    with pytest.raises(ValueError, match="neither a mixer nor a feed-forward"):
        nn.DecoderBlock(d, None, None)


# -- the share tests --------------------------------------------------------------------------

def _through(layer, leaves):
    """``state -> output`` of ``layer`` as ONE compiled program: the
    shares of a layer have the same shapes and differ in their values."""
    buffers = state_dict(layer, kind="buffer")
    fn = jax.jit(lambda ws, u: functional_call(
        layer, {**dict(zip(leaves, ws)), **buffers}, u)[0])

    def run(ws, u):
        with jax.default_matmul_precision("highest"):
            return fn(ws, u)
    return run


def test_four_head_shares_of_a_mixer_add_up_to_the_whole_layer(family):
    """The deployment's split of a mixer: rank r of 4 holds heads ``2 r,
    2 r + 1`` of 8 with group r of 4 (their z, x, B, C and dt columns of
    the input projection, their convolution channels, their norm group and
    their rows of the output projection); each computes its partial sum
    without the others, and the four add up to the uncut reference."""
    d, heads, p, groups, state, ranks = 32, 8, 4, 4, 8, 4
    whole = _mixer_weights(10, d, heads, p, groups, state)
    u = jnp.asarray(np.random.default_rng(11).standard_normal((1, 40, d)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda ws: family.mamba_mixer(
            u[0], ws, _mixer_conf(heads, p, groups, state)))(whole)
    inner, bc = heads * p, groups * state
    hs, gs = heads // ranks, groups // ranks

    def share(r):
        x_cols = np.arange(r * hs * p, (r + 1) * hs * p)
        g_cols = np.arange(r * gs * state, (r + 1) * gs * state)
        h_cols = np.arange(r * hs, (r + 1) * hs)
        conv = np.concatenate([x_cols, inner + g_cols, inner + bc + g_cols])
        rows = np.concatenate([x_cols, inner + conv,
                               2 * inner + 2 * bc + h_cols])
        w_conv, b_conv, a_log, d_skip, dt_bias, w_in, w_norm, w_out = whole
        return [w_conv[conv], b_conv[conv], a_log[h_cols], d_skip[h_cols],
                dt_bias[h_cols], w_in[rows], w_norm[x_cols], w_out[:, x_cols]]

    run = _through(nn.Mamba2Mixer(d, hs, p, gs, state), MIXER_LEAVES)
    parts = [run(share(r), u)[0] for r in range(ranks)]
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(parts[0] - want)).max() > 1e-2


def test_four_head_shares_of_attention_add_up_to_the_whole_layer(family):
    """8 query heads on 2 kv heads over 4 ranks: rank r holds query heads
    ``2 r, 2 r + 1`` on kv head ``r // 2`` (two ranks hold each kv head)."""
    d, h, g, dh, ranks = 32, 8, 2, 8, 4
    rng = np.random.default_rng(12)
    wq, wk, wv, wo = (draw(rng, h * dh, d, fan_in=d),
                      draw(rng, g * dh, d, fan_in=d),
                      draw(rng, g * dh, d, fan_in=d),
                      draw(rng, d, h * dh, fan_in=h * dh))
    u = jnp.asarray(rng.standard_normal((1, 24, d)), jnp.float32)
    conf = dict(head_dim=dh, num_attention_heads=h, num_key_value_heads=g)
    with jax.default_matmul_precision("highest"):
        want = family.attention(u[0], (wq, wk, wv, wo), conf)
    hs = h // ranks
    run = _through(
        nn.GroupedQueryAttention(d, hs, 1, dh, rotary=None, gate=None),
        ("q_proj.weight", "k_proj.weight", "v_proj.weight",
         "out_proj.weight"))
    parts = []
    for r in range(ranks):
        q = slice(r * hs * dh, (r + 1) * hs * dh)
        kv = slice((r * hs // (h // g)) * dh, (r * hs // (h // g) + 1) * dh)
        parts.append(run([wq[q], wk[kv], wv[kv], wo[:, q]], u)[0])
    np.testing.assert_allclose(sum(parts), want, rtol=1e-4, atol=1e-5)


def test_eight_expert_shares_add_up_to_the_uncut_latent_layer(family):
    """The guide's share test at the deployment's split in miniature:
    ranks 0-7 hold 2 experts each of 16, four a token by the biased
    sigmoid score; router, latent projections and shared expert are on
    every rank alike and counted ONCE: the ranks' routed parts (each
    already through the latent's output projection) add up, with one
    shared expert, to what the uncut reference gives for the whole
    layer, every assignment on exactly one rank."""
    d, latent, width, shared, n, k, t, ranks = 32, 16, 24, 40, 16, 4, 48, 8
    weights = _latent_weights(13, d, latent, width, shared, n)
    u = jnp.asarray(np.random.default_rng(14).standard_normal((t, d)),
                    jnp.float32)
    conf = _latent_conf(n, k)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda ws: family.latent_experts(u, ws, conf))(weights)
        once = family.shared_expert(u, weights[6:])
    count = n // ranks
    routed_only = _latent_layer(weights, d, latent, width, 0, n, k,
                                (0, count))
    buffers = state_dict(routed_only, kind="buffer")
    fn = jax.jit(lambda ws, first: _held_at(routed_only, first, ws, buffers,
                                            u), static_argnums=1)
    parts, rows = [], 0
    for r in range(ranks):
        first = r * count
        own = [w[first:first + count] for w in weights[:2]] + weights[2:6]
        with jax.default_matmul_precision("highest"):
            out, load = fn(own, first)
        parts.append(out)
        rows += int(np.asarray(load)[:-1].sum())
    assert rows == t * k                        # every assignment once
    np.testing.assert_allclose(sum(parts) + once, want, rtol=1e-4, atol=1e-5)


def _held_at(layer, first, ws, buffers, u):
    """The routed-only layer's output and load with its held experts
    starting at ``first`` (a static fact of the layer, set for the trace)."""
    layer.first = first
    out, state = functional_call(
        layer, {**dict(zip(LATENT_LEAVES[:6], ws)), **buffers}, u)
    return out, state["held_load"]


# -- the configuration and the plan ---------------------------------------------------------------

def test_the_configurations_counts_are_the_familys(family):
    with open(os.path.join(decoder_cases.ROOT, "benchmark", "configs",
                           "nemotron_3_super_120b_a12b.json")) as fh:
        conf = json.load(fh)
    sizes = [int(np.prod(s["shape"])) for s in family.param_specs(conf)]
    assert sum(sizes) == conf["parameters"] == 773582304
    flops = family.flops_per_record(conf)
    assert flops["total"] == conf["flops_per_record"]
    assert flops["total"] == flops["matrix_products"] + flops["attention"] \
        + flops["ssd"] + flops["convolution"]
    assert family.layers_of(conf) == ["sparse", "ssm"] * 5 + ["full"]
    pattern = conf["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (88, 40, 40, 8)
    assert pattern[conf["first_layer"]:][:11] == "EMEMEMEMEM*"
    # the widths are the published ones; the counts held are the shares
    assert {k: conf[k] for k in conf["reduced"]} == dict(
        num_hidden_layers=11, n_routed_experts=8, vocab_size=16384,
        mamba_num_heads=32, n_groups=2, num_attention_heads=8,
        num_key_value_heads=1)
    assert conf["published"] == dict(
        num_hidden_layers=88, n_routed_experts=512, vocab_size=131072,
        mamba_num_heads=128, n_groups=8, num_attention_heads=32,
        num_key_value_heads=2)
    assert conf["chunk_size"] == scan.CHUNK


def test_nemotron_h_plan_loss_and_every_gradient_match_the_reference(family):
    """``build_decoder_lm`` on the tiny cut (published layers 1-5 of
    ``MEMEM*EMEM*``: expert, mixer, expert, mixer, attention; 160
    positions, two chunks): the loss and every leaf's gradient, on seeded
    weights."""
    assert family.layers_of(tiny_conf()) == [
        "sparse", "ssm", "sparse", "ssm", "full"]
    check_loss_and_every_gradient(family, tiny_conf(), 15,
                                  zero_gradient_leaves=("expert_bias",))


def test_the_builder_knows_the_new_kinds_and_refuses_an_empty_layer():
    plan = models.tiny_decoder_plan(64)
    assert (plan.ssm_head_dim, plan.ssm_state, plan.ssm_groups, plan.ssm_conv,
            plan.expert_latent, plan.expert_activation) == (
        0, 0, 1, 4, None, "silu")
    empty = list(plan.layers) + [models.LayerPlan("none", 0, "none")]
    with pytest.raises(ValueError,
                       match="layer 4 has neither a mixer nor a feed-forward"):
        models.build_decoder_lm(plan._replace(layers=empty))
    model = models.build_decoder_lm(plan._replace(
        layers=[models.LayerPlan("ssm", 4, "none"),
                models.LayerPlan("none", 0, "sparse"),
                models.LayerPlan("full", 4, "none")],
        ssm_head_dim=8, ssm_state=16, ssm_groups=2, expert_latent=32,
        expert_activation="relu2", rotary_full=None, gate=None),
        remat=False)
    blocks = model.layers[1:4]
    assert [b.parts for b in blocks] == [("attn",), ("ffn",), ("attn",)]
    assert isinstance(blocks[0].attn, nn.Mamba2Mixer)
    assert (blocks[1].ffn.latent, blocks[1].ffn.gated,
            blocks[1].ffn.activation) == (32, False, "relu2")


# -- through the Optimizer, traced ------------------------------------------------------------------

def test_nemotron_h_plan_trains_through_local_optimizer_and_is_traced(
        tmp_path, family):
    """The tiny cut through ``LocalOptimizer``: the loss falls, the run log
    carries the scan's ``kernel/dispatch`` instants, the latent layer's
    ``moe/route`` facts and the per-step counters of both, and the
    Optimizer's own log the last step's."""
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
                                           4, 8, 16, 2)}
    attn = [e for e in legs if e["op"] == "attention"]
    assert {(e["q_heads"], e["kv_heads"], e["head_dim"], e["gate"],
             e["qk_norm"]) for e in attn} == {(4, 1, 16, None, False)}
    routes = [e for e in events if e.get("name") == "moe/route"]
    assert routes and {(e["latent"], e["activation"], e["gated"], e["score"],
                        e["select_bias"], e["shared"], e["top_k"])
                       for e in routes} == {
        (32, "relu2", False, "sigmoid", True, True, 4)}
    for name in ("ssm/decay_mean", "ssm/dt_mean", "ssm/state_norm_max"):
        seen = [e for e in events if e.get("name") == name]
        assert len(seen) == 6 * 2               # steps x mixers
        assert {e["layer"] for e in seen} == {"2.0.attn", "4.0.attn"}
        assert all(e["value"] > 0 for e in seen)
    assert len([m for m in said if "ssm/" in m]) == 2 * 3
    assert len([m for m in said if "moe/held_rows" in m]) == 2 * 3
