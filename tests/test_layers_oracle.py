"""Per-layer oracle tests against torch (CPU) — the analogue of the
reference's Lua-Torch subprocess oracle suite (``torch/TH.scala``,
SURVEY §4): same inputs, compare outputs and input-gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

import bigdl_tpu.nn as nn
from test_max_pool_ties import _pow2_grads


def _cmp(ours, theirs, rtol=1e-4, atol=1e-5):
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=rtol, atol=atol)


def _grad_cmp(layer, x_np, torch_fn, rtol=1e-4, atol=1e-5):
    """Compare d(sum(out))/d(input)."""
    x = jnp.asarray(x_np)
    gi = layer.backward(x, jnp.ones_like(layer.forward(x)))
    tx = torch.tensor(x_np, requires_grad=True)
    torch_fn(tx).sum().backward()
    _cmp(gi, tx.grad.numpy(), rtol, atol)


# ----------------------------- activations -------------------------------

ACT_CASES = [
    (nn.ReLU(), torch.relu),
    (nn.ReLU6(), lambda x: F.relu6(x)),
    (nn.Tanh(), torch.tanh),
    (nn.Sigmoid(), torch.sigmoid),
    (nn.ELU(), F.elu),
    (nn.LeakyReLU(0.02), lambda x: F.leaky_relu(x, 0.02)),
    (nn.SoftPlus(), F.softplus),
    (nn.SoftPlus(2.0), lambda x: F.softplus(x, beta=2.0)),
    (nn.LogSigmoid(), F.logsigmoid),
    (nn.TanhShrink(), F.tanhshrink),
    (nn.SoftSign(), F.softsign),
    (nn.SoftShrink(0.4), lambda x: F.softshrink(x, 0.4)),
    (nn.HardShrink(0.4), lambda x: F.hardshrink(x, 0.4)),
    (nn.HardTanh(-2.0, 2.0), lambda x: F.hardtanh(x, -2.0, 2.0)),
    (nn.SoftMax(), lambda x: F.softmax(x, dim=1)),
    (nn.LogSoftMax(), lambda x: F.log_softmax(x, dim=1)),
    (nn.SoftMin(), lambda x: F.softmin(x, dim=1)),
]


@pytest.mark.parametrize("case", ACT_CASES, ids=lambda c: type(c[0]).__name__ + str(id(c))[-3:])
def test_activation_forward_backward(case):
    layer, ref = case
    x = np.random.randn(4, 7).astype(np.float32)
    _cmp(layer.forward(jnp.asarray(x)), ref(torch.tensor(x)).numpy())
    _grad_cmp(layer, x, ref)


def test_prelu():
    layer = nn.PReLU(5)
    x = np.random.randn(3, 5, 4).astype(np.float32)
    ref = F.prelu(torch.tensor(x), torch.tensor(np.asarray(layer.weight)))
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy())


# ----------------------------- convolutions ------------------------------

def test_spatial_convolution_matches_torch():
    layer = nn.SpatialConvolution(3, 8, 3, 3, 2, 2, 1, 1)
    x = np.random.randn(2, 3, 9, 9).astype(np.float32)
    ref = F.conv2d(torch.tensor(x), torch.tensor(np.asarray(layer.weight)),
                   torch.tensor(np.asarray(layer.bias)), stride=2, padding=1)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy(), rtol=1e-3, atol=1e-4)


def test_spatial_convolution_groups_nhwc():
    layer = nn.SpatialConvolution(4, 8, 3, 3, 1, 1, 0, 0, n_group=2, format="NHWC")
    x = np.random.randn(2, 7, 7, 4).astype(np.float32)
    ref = F.conv2d(torch.tensor(x.transpose(0, 3, 1, 2)),
                   torch.tensor(np.asarray(layer.weight)),
                   torch.tensor(np.asarray(layer.bias)), groups=2)
    out = layer.forward(jnp.asarray(x))
    _cmp(np.asarray(out).transpose(0, 3, 1, 2), ref.numpy(), rtol=1e-3, atol=1e-4)


def test_conv_grads_match_torch():
    layer = nn.SpatialConvolution(2, 4, 3, 3)
    x = np.random.randn(1, 2, 6, 6).astype(np.float32)
    layer.zero_grad_parameters()
    out = layer.forward(jnp.asarray(x))
    layer.backward(jnp.asarray(x), jnp.ones_like(out))
    tw = torch.tensor(np.asarray(layer.weight), requires_grad=True)
    tb = torch.tensor(np.asarray(layer.bias), requires_grad=True)
    tx = torch.tensor(x, requires_grad=True)
    F.conv2d(tx, tw, tb).sum().backward()
    _cmp(layer._grads["weight"], tw.grad.numpy(), rtol=1e-3, atol=1e-4)
    _cmp(layer._grads["bias"], tb.grad.numpy(), rtol=1e-3, atol=1e-4)
    _cmp(layer.grad_input, tx.grad.numpy(), rtol=1e-3, atol=1e-4)


def test_full_convolution_matches_torch():
    layer = nn.SpatialFullConvolution(4, 6, 3, 3, 2, 2, 1, 1, 1, 1)
    x = np.random.randn(2, 4, 5, 5).astype(np.float32)
    ref = F.conv_transpose2d(torch.tensor(x), torch.tensor(np.asarray(layer.weight)),
                             torch.tensor(np.asarray(layer.bias)),
                             stride=2, padding=1, output_padding=1)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy(), rtol=1e-3, atol=1e-4)


def test_dilated_convolution_matches_torch():
    layer = nn.SpatialDilatedConvolution(3, 5, 3, 3, 1, 1, 2, 2, 2, 2)
    x = np.random.randn(1, 3, 9, 9).astype(np.float32)
    ref = F.conv2d(torch.tensor(x), torch.tensor(np.asarray(layer.weight)),
                   torch.tensor(np.asarray(layer.bias)), padding=2, dilation=2)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy(), rtol=1e-3, atol=1e-4)


def test_temporal_convolution_matches_torch():
    layer = nn.TemporalConvolution(6, 4, 3, 2)
    x = np.random.randn(2, 11, 6).astype(np.float32)
    # torch conv1d is NCW with weight (out, in, k)
    ref = F.conv1d(torch.tensor(x.transpose(0, 2, 1)),
                   torch.tensor(np.asarray(layer.weight)),
                   torch.tensor(np.asarray(layer.bias)), stride=2)
    _cmp(np.asarray(layer.forward(jnp.asarray(x))).transpose(0, 2, 1),
         ref.numpy(), rtol=1e-3, atol=1e-4)


def test_volumetric_convolution_matches_torch():
    layer = nn.VolumetricConvolution(2, 4, 3, 3, 3, 1, 1, 1, 1, 1, 1)
    x = np.random.randn(1, 2, 5, 5, 5).astype(np.float32)
    ref = F.conv3d(torch.tensor(x), torch.tensor(np.asarray(layer.weight)),
                   torch.tensor(np.asarray(layer.bias)), padding=1)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy(), rtol=1e-3, atol=1e-4)


# ----------------------------- pooling -----------------------------------

def test_max_pooling_matches_torch():
    layer = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)
    x = np.random.randn(2, 3, 9, 9).astype(np.float32)
    ref = F.max_pool2d(torch.tensor(x), 3, 2, 1)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy())


def test_max_pooling_ceil_mode():
    layer = nn.SpatialMaxPooling(3, 3, 2, 2).ceil()
    x = np.random.randn(1, 2, 8, 8).astype(np.float32)
    ref = F.max_pool2d(torch.tensor(x), 3, 2, 0, ceil_mode=True)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy())


def test_avg_pooling_matches_torch():
    layer = nn.SpatialAveragePooling(2, 2, 2, 2)
    x = np.random.randn(2, 3, 8, 8).astype(np.float32)
    ref = F.avg_pool2d(torch.tensor(x), 2, 2)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy())


def test_avg_pooling_pad_count_exclude():
    layer = nn.SpatialAveragePooling(3, 3, 2, 2, 1, 1, count_include_pad=False)
    x = np.random.randn(1, 1, 7, 7).astype(np.float32)
    ref = F.avg_pool2d(torch.tensor(x), 3, 2, 1, count_include_pad=False)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy())


CELL_POOLS = {
    # the pool geometries the benchmark's CNN cells run, at small
    # channel counts: (layer, torch's, input plane)
    "stem_3x3_s2_ceil": (
        lambda: nn.SpatialMaxPooling(3, 3, 2, 2).ceil(),
        lambda x: F.max_pool2d(x, 3, 2, 0, ceil_mode=True), 16),
    "branch_3x3_s1_pad1": (
        lambda: nn.SpatialMaxPooling(3, 3, 1, 1, 1, 1).ceil(),
        lambda x: F.max_pool2d(x, 3, 1, 1, ceil_mode=True), 14),
    "resnet_3x3_s2_pad1": (
        lambda: nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1),
        lambda x: F.max_pool2d(x, 3, 2, 1), 16),
    "aux_head_5x5_s3_avg": (
        lambda: nn.SpatialAveragePooling(5, 5, 3, 3).ceil(),
        lambda x: F.avg_pool2d(x, 5, 3, 0, ceil_mode=True), 14),
    "head_7x7_avg": (
        lambda: nn.SpatialAveragePooling(7, 7, 1, 1),
        lambda x: F.avg_pool2d(x, 7, 1), 7),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("site", CELL_POOLS)
def test_cell_pool_geometries_match_torch(site, dtype):
    """Value and input gradient of each pool a CNN cell runs, in the
    cells' dtype too; torch computes in float32 on the same (bf16-grid)
    numbers and a cotangent of powers of two, so a max pool must agree
    bitwise, first-argmax ties included."""
    build, ref, plane = CELL_POOLS[site]
    rng = np.random.default_rng(3)
    x = jnp.asarray(np.round(rng.normal(size=(2, 8, plane, plane)) * 4) / 4,
                    dtype)
    y, vjp = jax.vjp(jax.jit(build().update_output), x)
    tx = torch.tensor(np.asarray(x, np.float32), requires_grad=True)
    ty = ref(tx)
    gy = _pow2_grads(rng, y.shape, dtype)
    ty.backward(torch.tensor(np.asarray(gy, np.float32)))
    exact = "avg" not in site
    tol = dict(rtol=0, atol=0) if exact \
        else dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-6)
    assert y.dtype == dtype
    _cmp(np.asarray(y, np.float32), ty.detach().numpy(), **tol)
    _cmp(np.asarray(vjp(gy)[0], np.float32), tx.grad.numpy(), **tol)


def test_volumetric_max_pooling():
    layer = nn.VolumetricMaxPooling(2, 2, 2)
    x = np.random.randn(1, 2, 4, 4, 4).astype(np.float32)
    ref = F.max_pool3d(torch.tensor(x), 2, 2)
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy())


# ----------------------------- normalization ------------------------------

def test_batchnorm_train_and_eval_match_torch():
    layer = nn.BatchNormalization(5, eps=1e-5, momentum=0.1)
    tbn = torch.nn.BatchNorm1d(5, eps=1e-5, momentum=0.1)
    x = np.random.randn(8, 5).astype(np.float32)
    out = layer.forward(jnp.asarray(x))
    ref = tbn(torch.tensor(x))
    _cmp(out, ref.detach().numpy(), rtol=1e-3, atol=1e-4)
    _cmp(layer.running_mean, tbn.running_mean.numpy(), rtol=1e-3, atol=1e-5)
    _cmp(layer.running_var, tbn.running_var.numpy(), rtol=1e-3, atol=1e-5)
    layer.evaluate(); tbn.eval()
    x2 = np.random.randn(4, 5).astype(np.float32)
    _cmp(layer.forward(jnp.asarray(x2)), tbn(torch.tensor(x2)).detach().numpy(),
         rtol=1e-3, atol=1e-4)


def test_spatial_batchnorm_matches_torch():
    layer = nn.SpatialBatchNormalization(3)
    tbn = torch.nn.BatchNorm2d(3)
    x = np.random.randn(4, 3, 5, 5).astype(np.float32)
    _cmp(layer.forward(jnp.asarray(x)), tbn(torch.tensor(x)).detach().numpy(),
         rtol=1e-3, atol=1e-4)


def test_cross_map_lrn_matches_torch():
    layer = nn.SpatialCrossMapLRN(5, 0.0001, 0.75, 1.0)
    x = np.random.randn(2, 7, 4, 4).astype(np.float32)
    ref = torch.nn.LocalResponseNorm(5, 0.0001, 0.75, 1.0)(torch.tensor(x))
    _cmp(layer.forward(jnp.asarray(x)), ref.numpy(), rtol=1e-4, atol=1e-5)


def test_max_pooling_backward_matches_torch():
    """The opt-in tie-split VJP (residue-class gather backward) must agree
    with the torch oracle on continuous inputs (ties have measure zero)."""
    for kw, kh, dw, dh, pw, ph, ceil in [(3, 3, 2, 2, 1, 1, False),
                                         (3, 3, 1, 1, 1, 1, False),
                                         (3, 3, 2, 2, 0, 0, True),
                                         (2, 2, 2, 2, 0, 0, False)]:
        layer = nn.SpatialMaxPooling(kw, kh, dw, dh, pw, ph).split_ties()
        if ceil:
            layer.ceil()
        assert layer.tie_split
        x_np = np.random.randn(2, 3, 9, 9).astype(np.float32)
        _grad_cmp(layer, x_np,
                  lambda t: F.max_pool2d(t, (kh, kw), (dh, dw), (ph, pw),
                                         ceil_mode=ceil))


def test_max_pooling_tie_split_conserves_gradient():
    """With ties, split_ties() divides the cotangent equally among maxima
    — total gradient mass equals the torch first-argmax convention."""
    layer = nn.SpatialMaxPooling(2, 2, 2, 2).split_ties()
    x = jnp.ones((1, 1, 4, 4), jnp.float32)  # every window fully tied
    g = layer.backward(x, jnp.ones((1, 1, 2, 2), jnp.float32))
    assert float(jnp.sum(g)) == pytest.approx(4.0)
    np.testing.assert_allclose(np.asarray(g), 0.25 * np.ones((1, 1, 4, 4)))


def test_max_pooling_torch_ties_path():
    layer = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1).torch_ties()
    x_np = np.random.randn(2, 3, 9, 9).astype(np.float32)
    _grad_cmp(layer, x_np, lambda t: F.max_pool2d(t, 3, 2, 1))


def test_cross_map_lrn_backward_and_variants():
    """The banded-matmul LRN (MXU path): backward vs torch, NHWC layout,
    and the generic-beta fallback."""
    x_np = np.random.randn(2, 7, 4, 4).astype(np.float32)
    _grad_cmp(nn.SpatialCrossMapLRN(5, 0.0001, 0.75, 1.0), x_np,
              lambda t: torch.nn.LocalResponseNorm(5, 0.0001, 0.75, 1.0)(t))
    # beta != 0.75 exercises the jnp.power fallback
    _grad_cmp(nn.SpatialCrossMapLRN(3, 0.001, 0.5, 2.0), x_np,
              lambda t: torch.nn.LocalResponseNorm(3, 0.001, 0.5, 2.0)(t))
    # NHWC agrees with NCHW
    lrn_c = nn.SpatialCrossMapLRN(5, 0.0001, 0.75, 1.0)
    lrn_l = nn.SpatialCrossMapLRN(5, 0.0001, 0.75, 1.0, format="NHWC")
    out_c = lrn_c.forward(jnp.asarray(x_np))
    out_l = lrn_l.forward(jnp.asarray(x_np.transpose(0, 2, 3, 1)))
    _cmp(jnp.transpose(out_l, (0, 3, 1, 2)), out_c)


def test_dropout_keeps_expectation():
    layer = nn.Dropout(0.4)
    x = jnp.ones((1000, 20))
    out = layer.forward(x)
    kept = np.asarray(out) != 0
    assert abs(kept.mean() - 0.6) < 0.05
    np.testing.assert_allclose(np.asarray(out)[kept], 1.0 / 0.6, rtol=1e-5)
    layer.evaluate()
    np.testing.assert_array_equal(np.asarray(layer.forward(x)), np.asarray(x))


def test_normalize_matches_torch():
    layer = nn.Normalize(2.0)
    x = np.random.randn(4, 6).astype(np.float32)
    _cmp(layer.forward(jnp.asarray(x)), F.normalize(torch.tensor(x), 2.0).numpy())


# ----------------------------- rnn ----------------------------------------

def sync_lstm_to_torch(cell, tl):
    """Copy our packed (i,f,g,o) LSTM cell weights into a torch LSTM —
    the ONE copy of the gate-packing contract shared by the fixed
    oracles and the shape fuzz."""
    with torch.no_grad():
        tl.weight_ih_l0.copy_(torch.tensor(np.asarray(cell.i2g.weight)))
        tl.bias_ih_l0.copy_(torch.tensor(np.asarray(cell.i2g.bias)))
        tl.weight_hh_l0.copy_(torch.tensor(np.asarray(cell.h2g.weight)))
        tl.bias_hh_l0.zero_()


def sync_gru_to_torch(cell, tg):
    """Copy our (r,z | n) GRU cell weights into a torch GRU."""
    with torch.no_grad():
        tg.weight_ih_l0.copy_(torch.tensor(np.asarray(cell.i2g.weight)))
        tg.bias_ih_l0.copy_(torch.tensor(np.asarray(cell.i2g.bias)))
        w_hh = np.concatenate([np.asarray(cell.h2rz.weight),
                               np.asarray(cell.h2n.weight)])
        tg.weight_hh_l0.copy_(torch.tensor(w_hh))
        tg.bias_hh_l0.zero_()



def test_lstm_matches_torch():
    hidden, inp = 7, 5
    cell = nn.LSTM(inp, hidden)
    rec = nn.Recurrent(cell)
    x = np.random.randn(3, 6, inp).astype(np.float32)

    tl = torch.nn.LSTM(inp, hidden, batch_first=True)
    sync_lstm_to_torch(cell, tl)
    out = rec.forward(jnp.asarray(x))
    ref, _ = tl(torch.tensor(x))
    _cmp(out, ref.detach().numpy(), rtol=1e-3, atol=1e-4)


def test_lstm_backward_matches_torch():
    """Input gradients THROUGH the lax.scan time loop vs torch's
    unrolled backward."""
    hidden, inp = 7, 5
    cell = nn.LSTM(inp, hidden)
    rec = nn.Recurrent(cell)
    x_np = np.random.randn(3, 6, inp).astype(np.float32)
    gy = np.random.randn(3, 6, hidden).astype(np.float32)

    tl = torch.nn.LSTM(inp, hidden, batch_first=True)
    sync_lstm_to_torch(cell, tl)
    gx = rec.backward(jnp.asarray(x_np), jnp.asarray(gy))
    tx = torch.tensor(x_np, requires_grad=True)
    out, _ = tl(tx)
    out.backward(torch.tensor(gy))
    _cmp(gx, tx.grad.numpy(), rtol=1e-3, atol=1e-4)


def test_gru_matches_torch():
    hidden, inp = 4, 3
    cell = nn.GRU(inp, hidden)
    rec = nn.Recurrent(cell)
    x = np.random.randn(2, 5, inp).astype(np.float32)
    tg = torch.nn.GRU(inp, hidden, batch_first=True)
    sync_gru_to_torch(cell, tg)
    out = rec.forward(jnp.asarray(x))
    ref, _ = tg(torch.tensor(x))
    _cmp(out, ref.detach().numpy(), rtol=1e-3, atol=1e-4)


def test_gru_backward_matches_torch():
    hidden, inp = 4, 3
    cell = nn.GRU(inp, hidden)
    rec = nn.Recurrent(cell)
    x_np = np.random.randn(2, 5, inp).astype(np.float32)
    gy = np.random.randn(2, 5, hidden).astype(np.float32)
    tg = torch.nn.GRU(inp, hidden, batch_first=True)
    sync_gru_to_torch(cell, tg)
    gx = rec.backward(jnp.asarray(x_np), jnp.asarray(gy))
    tx = torch.tensor(x_np, requires_grad=True)
    out, _ = tg(tx)
    out.backward(torch.tensor(gy))
    _cmp(gx, tx.grad.numpy(), rtol=1e-3, atol=1e-4)


def test_rnn_cell_and_birecurrent_shapes():
    rec = nn.Recurrent(nn.RnnCell(4, 6))
    x = jnp.asarray(np.random.randn(2, 5, 4).astype(np.float32))
    assert rec.forward(x).shape == (2, 5, 6)
    bi = nn.BiRecurrent().with_cell(nn.LSTM(4, 6))
    assert bi.forward(x).shape == (2, 5, 12)


def test_recurrent_decoder_shape():
    dec = nn.RecurrentDecoder(4, nn.LSTM(5, 5))
    x = jnp.asarray(np.random.randn(2, 5).astype(np.float32))
    assert dec.forward(x).shape == (2, 4, 5)


def test_recurrent_under_jit_and_grad():
    from bigdl_tpu.nn.module import functional_call, state_dict

    rec = nn.Recurrent(nn.LSTM(3, 4))
    x = jnp.asarray(np.random.randn(2, 5, 3).astype(np.float32))
    p = state_dict(rec)

    @jax.jit
    def loss(p):
        out, _ = functional_call(rec, p, x)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(p)
    assert g["0.i2g.weight"].shape == (16, 3)
    assert float(loss(p)) > 0


# ----------------------------- graph / containers -------------------------

def test_graph_dag_forward_backward():
    inp = nn.Input()
    fc1 = nn.Linear(4, 8).set_name("fc1").inputs(inp)
    act = nn.ReLU().inputs(fc1)
    fc2 = nn.Linear(8, 2).set_name("fc2").inputs(act)
    model = nn.Graph(inp, fc2)
    x = jnp.ones((3, 4))
    out = model.forward(x)
    assert out.shape == (3, 2)
    seq = nn.Sequential(model["fc1"], nn.ReLU(), model["fc2"])
    _cmp(out, seq.forward(x))
    model.zero_grad_parameters()
    model.backward(x, jnp.ones((3, 2)))
    assert "weight" in model["fc1"]._grads


def test_graph_multi_input_output():
    a, b = nn.Input(), nn.Input()
    s = nn.CAddTable().inputs(a, b)
    m = nn.CMulTable().inputs(a, b)
    model = nn.Graph([a, b], [s, m])
    x, y = jnp.ones((2, 3)), jnp.full((2, 3), 2.0)
    out_s, out_m = model.forward([x, y])
    _cmp(out_s, np.full((2, 3), 3.0))
    _cmp(out_m, np.full((2, 3), 2.0))


def test_graph_stop_gradient():
    inp = nn.Input()
    fc1 = nn.Linear(3, 3).set_name("fc1").inputs(inp)
    fc2 = nn.Linear(3, 2).set_name("fc2").inputs(fc1)
    model = nn.Graph(inp, fc2).stop_gradient(["fc1"])
    x = jnp.ones((2, 3))
    model.zero_grad_parameters()
    model.forward(x)
    model.backward(x, jnp.ones((2, 2)))
    assert "weight" not in model["fc1"]._grads or \
        np.allclose(np.asarray(model["fc1"]._grads["weight"]), 0.0)
    assert "weight" in model["fc2"]._grads


def test_concat_and_table_containers():
    c = nn.Concat(1).add(nn.Linear(4, 3)).add(nn.Linear(4, 5))
    x = jnp.ones((2, 4))
    assert c.forward(x).shape == (2, 8)
    ct = nn.ConcatTable().add(nn.Identity()).add(nn.MulConstant(2.0))
    out = ct.forward(x)
    _cmp(out[1], 2 * np.asarray(out[0]))
    pt = nn.ParallelTable().add(nn.MulConstant(2.0)).add(nn.MulConstant(3.0))
    out = pt.forward([x, x])
    _cmp(out[0] * 1.5, out[1])


def test_shape_layers():
    x = jnp.asarray(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
    assert nn.Reshape((12,)).forward(x).shape == (2, 12)
    assert nn.Transpose([(1, 2)]).forward(x).shape == (2, 4, 3)
    assert nn.Select(1, 0).forward(x).shape == (2, 4)
    assert nn.Narrow(2, 1, 2).forward(x).shape == (2, 3, 2)
    assert nn.Squeeze().forward(jnp.ones((2, 1, 3))).shape == (2, 3)
    assert nn.Unsqueeze(1).forward(x).shape == (2, 1, 3, 4)
    parts = nn.SplitTable(1).forward(x)
    assert len(parts) == 3 and parts[0].shape == (2, 4)
    joined = nn.JoinTable(1).forward(parts)
    assert joined.shape == (2, 12)
    infer = nn.InferReshape((0, -1), batch_mode=False).forward(x)
    assert infer.shape == (2, 12)


def test_global_max_pooling_uses_fallback_and_matches():
    """Window taps above the gate (global pooling) must route to the
    reduce_window autodiff path, with identical forward results."""
    layer = nn.SpatialMaxPooling(1, 1, global_pooling=True)
    x_np = np.random.randn(2, 3, 16, 16).astype(np.float32)
    out = layer.forward(jnp.asarray(x_np))
    np.testing.assert_allclose(np.asarray(out).reshape(2, 3),
                               x_np.max(axis=(2, 3)))
    g = layer.backward(jnp.asarray(x_np), jnp.ones_like(out))
    assert float(jnp.sum(g)) == pytest.approx(6.0)
