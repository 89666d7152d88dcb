"""Sparse embedding fast path (nn/layers/embedding.py + the TrainStep
sparse-sync leg + lazy row-wise optimizer applies; ISSUE 15,
docs/sparse.md).

The contract under test, in order of importance:

1. **Numerics-exact**: N training steps under the sparse (indices,
   rows) sync equal the dense-all-reduce path (rtol 1e-6; Adagrad/Adam
   bit-equal) — duplicate indices and the padding index included, on a
   single device AND on multi-device meshes across every
   ``parameter_sync`` layout (the 2-process gloo leg lives in
   ``tests/test_multihost.py``).
2. **The measured win**: the PR-10 comms walker shows the table's
   per-step sync bytes collapsing >= 10x on a 2-device mesh.
3. The row-sparse cotangent itself (``test_numeric_grads.py``
   discipline): finite differences + scatter-equivalence against the
   dense cotangent.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

_enable_x64 = jax.enable_x64

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.nn.layers import embedding as embed
from bigdl_tpu.parallel.mesh import DATA_AXIS, make_mesh
from bigdl_tpu.parallel.train_step import TrainStep
from bigdl_tpu.utils.config import BigDLConfig, set_config
from bigdl_tpu.utils.rng import RNG

V, D, CLASSES = 128, 8, 4


@pytest.fixture(autouse=True)
def _fresh_config():
    set_config(None)
    yield
    set_config(None)


def _cfg(sparse_mode: str):
    set_config(BigDLConfig.from_env({"BIGDL_SPARSE": sparse_mode}))


def _classifier(vocab=V, dim=D, padding_idx=None, sparse=None,
                max_norm=float("inf"), w_regularizer=None):
    RNG.set_seed(0)
    return nn.Sequential(
        nn.LookupTable(vocab, dim, padding_idx=padding_idx, sparse=sparse,
                       max_norm=max_norm, w_regularizer=w_regularizer),
        nn.Select(1, -1), nn.Linear(dim, CLASSES), nn.LogSoftMax())


def _batch(vocab=V, batch=16, seq=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    x[0, :2] = 5   # duplicate indices inside one batch
    x[1, 0] = 0    # the padding index (when configured)
    y = rng.randint(0, CLASSES, batch)
    return x, y


def _train(model_fn, mode, steps=5, mesh_size=0, sync="allreduce",
           method=None, rules=None, batch=None, **step_kw):
    _cfg(mode)
    x, y = batch if batch is not None else _batch()
    mesh = (make_mesh((mesh_size,), (DATA_AXIS,),
                      devices=jax.devices()[:mesh_size])
            if mesh_size else None)
    st = TrainStep(model_fn(), nn.ClassNLLCriterion(),
                   method() if method else optim.SGD(0.1, momentum=0.9),
                   mesh=mesh, parameter_sync=sync,
                   extra_sharding_rules=rules, **step_kw)
    loss = None
    for _ in range(steps):
        loss = st.run(x, y, jax.random.key(3))
    params = {k: np.asarray(v)
              for k, v in st.gather_replicated(st.params).items()}
    return params, float(loss), st


def _assert_params_close(a, b, rtol=1e-6, atol=1e-7):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=rtol, atol=atol,
                                   err_msg=k)


# -- 1. numerics-exact sparse vs dense ---------------------------------------
def test_multistep_sparse_matches_dense_sgd_momentum():
    fn = lambda: _classifier(padding_idx=0)  # noqa: E731
    dense, ld, _ = _train(fn, "off")
    sparse, ls, st = _train(fn, "on")
    assert st._sparse_stats, "sparse path did not engage"
    assert ld == pytest.approx(ls, rel=1e-6)
    _assert_params_close(dense, sparse)


@pytest.mark.parametrize("method,bitexact", [
    (lambda: optim.Adagrad(0.1), True),     # lazy row-wise apply
    (lambda: optim.SGD(0.1), False),        # row-wise p[u] -= lr*g
    (lambda: optim.Adam(0.01), True),       # densify-locally fallback
    (lambda: optim.SGD(0.1, momentum=0.9, nesterov=True), False),
])
def test_multistep_sparse_matches_dense_per_method(method, bitexact):
    fn = lambda: _classifier(padding_idx=0)  # noqa: E731
    dense, _, _ = _train(fn, "off", method=method)
    sparse, _, _ = _train(fn, "on", method=method)
    _assert_params_close(dense, sparse)
    if bitexact:
        for k in dense:
            assert np.array_equal(dense[k], sparse[k]), (
                f"{k}: lazy apply must reproduce the dense update "
                f"bit-for-bit for this method")


@pytest.mark.parametrize("kw,tol", [
    ({"remat": True}, 1e-6),              # capture under jax.checkpoint
    ({"compute_dtype": jnp.bfloat16}, 1e-2),  # the bench recipe's dtype
], ids=["remat", "bf16"])
def test_multistep_sparse_matches_dense_composed(kw, tol):
    fn = _classifier
    dense, _, _ = _train(fn, "off", **kw)
    sparse, _, st = _train(fn, "on", **kw)
    assert st._sparse_stats
    _assert_params_close(dense, sparse, rtol=tol, atol=tol)


@pytest.mark.parametrize("mesh_size,sync", [
    (2, "allreduce"), (4, "sharded"), (2, "fsdp")])
def test_multistep_sparse_matches_dense_on_mesh(mesh_size, sync):
    fn = _classifier
    dense, _, _ = _train(fn, "off", mesh_size=mesh_size, sync=sync)
    sparse, _, _ = _train(fn, "on", mesh_size=mesh_size, sync=sync)
    _assert_params_close(dense, sparse)


def test_row_sharded_table_matches_replicated_dense():
    """The table row-sharded over the data axis (PartitionSpec via
    row_sharding_rules) + sparse sync == the replicated dense run."""
    fn = _classifier
    dense, _, _ = _train(fn, "off", mesh_size=2)
    model = fn()
    rules = embed.row_sharding_rules(model, axis=DATA_AXIS)
    _cfg("on")
    x, y = _batch()
    mesh = make_mesh((2,), (DATA_AXIS,), devices=jax.devices()[:2])
    st = TrainStep(model, nn.ClassNLLCriterion(),
                   optim.SGD(0.1, momentum=0.9), mesh=mesh,
                   extra_sharding_rules=rules)
    for _ in range(5):
        st.run(x, y, jax.random.key(3))
    sparse = {k: np.asarray(v)
              for k, v in st.gather_replicated(st.params).items()}
    _assert_params_close(dense, sparse)
    # the table really is sharded: each leaf's committed sharding
    # splits dim 0 (vocab) over the data axis
    spec = st._param_sharding("0.weight", st.params["0.weight"]).spec
    assert tuple(spec)[0] == DATA_AXIS


def test_pure_embedding_model_all_params_sparse():
    """Every parameter sparse: update_mixed's dense leg runs over an
    empty tree and counters still advance exactly once."""
    def fn():
        RNG.set_seed(0)
        return nn.Sequential(nn.EmbeddingBag(V, CLASSES, mode="mean"),
                             nn.LogSoftMax())
    dense, _, _ = _train(fn, "off", steps=3)
    sparse, _, st = _train(fn, "on", steps=3)
    _assert_params_close(dense, sparse)
    assert int(st.opt_state["neval"]) == 3


# -- 2. the measured win (PR-10 comms walker) --------------------------------
def test_comms_bytes_drop_at_least_10x_on_mesh():
    from bigdl_tpu.telemetry import comms

    def facts(mode):
        _cfg(mode)
        RNG.set_seed(0)
        model = nn.Sequential(nn.LookupTable(4096, 32), nn.Select(1, -1),
                              nn.Linear(32, CLASSES), nn.LogSoftMax())
        mesh = make_mesh((2,), (DATA_AXIS,), devices=jax.devices()[:2])
        st = TrainStep(model, nn.ClassNLLCriterion(),
                       optim.SGD(0.1, momentum=0.9), mesh=mesh)
        x, y = _batch(vocab=4096, batch=16, seq=8)
        compiled = st._build().lower(
            st.params, st.opt_state, st.buffers, *st._shard_batch(x, y),
            jax.random.key(0)).compile()
        return comms.comms_facts(compiled, mesh=mesh, model=st.model)

    dense, sparse = facts("off"), facts("auto")
    assert dense["bytes"] >= 10 * sparse["bytes"], (
        f"sparse sync must cut step comms >= 10x here: "
        f"dense={dense['bytes']} sparse={sparse['bytes']}")
    # no collective in the sparse program moves table-scale payload
    table_payload = 4096 * 32 * 4
    assert all(r["payload_bytes"] < table_payload
               for r in sparse["rows"]), sparse["rows"]


def test_attribute_comms_model_sparse_ab_on_dlrm():
    """The CLI-backing A/B: dlrm's registry-scale tables at mesh 2 —
    the sparse leg moves <10% of the dense leg's bytes and restores
    the prior config afterwards."""
    from bigdl_tpu.telemetry import comms
    from bigdl_tpu.utils.config import get_config

    before = get_config().sparse_sync
    dense = comms.attribute_comms_model("dlrm", batch=32, devices=2,
                                        sparse="off")
    sparse = comms.attribute_comms_model("dlrm", batch=32, devices=2,
                                         sparse="auto")
    assert get_config().sparse_sync == before
    assert dense["bytes"] >= 10 * sparse["bytes"]
    assert sparse["sparse"] == "auto"
    # the embedding tables own the surviving (small) sync rows
    assert any(r["path"].startswith("embed_") for r in sparse["rows"])


# -- 3. the row-sparse cotangent itself --------------------------------------
def _capture_rows_fn(layer, idx):
    """f(proxy) -> scalar loss through the layer's sparse path, plus the
    recorded unique indices — the differentiable view of the row-sparse
    cotangent."""
    paths = {id(layer): "weight"}
    shapes, _ = embed.discover_proxies(
        lambda: layer.update_output(idx), paths)
    (key, sds), = shapes.items()

    def f(proxy):
        with embed.SparseCapture(paths, {key: proxy}) as cap:
            out = layer.update_output(idx)
            u = cap.aux[key]["u"]
        return jnp.sum(jnp.sin(out)), u

    return f, sds


@pytest.mark.parametrize("build", [
    lambda: nn.LookupTable(11, 3, sparse=True, padding_idx=2),
    lambda: nn.EmbeddingBag(11, 3, mode="sum", sparse=True,
                            padding_idx=2),
    lambda: nn.EmbeddingBag(11, 3, mode="mean", sparse=True,
                            padding_idx=2),
], ids=["lookup", "bag_sum", "bag_mean"])
def test_sparse_vjp_matches_finite_differences_and_dense(build):
    from jax.test_util import check_grads

    RNG.set_seed(0)
    with _enable_x64():
        layer = build().evaluate()
        # duplicates (7 twice in row 0) AND the padding index (2)
        idx = jnp.asarray(np.array([[7, 7, 2, 1], [3, 4, 4, 2]],
                                   dtype=np.int32))
        f, sds = _capture_rows_fn(layer, idx)
        proxy0 = jnp.zeros(sds.shape, jnp.float64)
        check_grads(lambda p: f(p)[0], (proxy0,), order=1,
                    modes=("rev",), atol=1e-3, rtol=1e-3)
        g_rows, u = jax.grad(f, has_aux=True)(proxy0)
        # padding row's cotangent is zeroed INSIDE the VJP
        pad_slots = np.asarray(u) == 2
        assert pad_slots.any()
        assert np.all(np.asarray(g_rows)[pad_slots] == 0.0)
        # scatter-equivalence: rows scattered onto their indices ==
        # the DENSE path's table cotangent (duplicates pre-summed)
        dense_tab = layer.weight

        def dense_loss(w):
            layer.weight = w
            try:
                return jnp.sum(jnp.sin(layer.update_output(idx)))
            finally:
                layer.weight = dense_tab
        g_dense = jax.grad(dense_loss)(dense_tab)
        scattered = jnp.zeros_like(dense_tab).at[u].add(
            g_rows.astype(dense_tab.dtype), mode="drop")
        # f32 table: the bag reduction orders its sums differently on
        # the two paths, so equivalence is to f32 round-off
        np.testing.assert_allclose(np.asarray(scattered),
                                   np.asarray(g_dense), rtol=1e-5,
                                   atol=1e-7)


def test_embedding_bag_forward_reference():
    """sum/mean vs a numpy reference, padding entries excluded from the
    value AND the mean denominator."""
    RNG.set_seed(0)
    w = np.random.RandomState(1).randn(9, 4).astype(np.float32)
    idx = np.array([[1, 2, 0, 2], [0, 0, 0, 3]], dtype=np.int32)
    for mode in ("sum", "mean"):
        bag = nn.EmbeddingBag(9, 4, mode=mode, padding_idx=0)
        bag.weight = jnp.asarray(w)
        out = np.asarray(bag.update_output(jnp.asarray(idx)))
        ref = np.zeros((2, 4), np.float32)
        for r in range(2):
            rows = [w[i] for i in idx[r] if i != 0]
            if rows:
                ref[r] = np.sum(rows, axis=0)
                if mode == "mean":
                    ref[r] /= len(rows)
        np.testing.assert_allclose(out, ref, rtol=1e-6)
    # 1-D input treated as bag size 1
    bag = nn.EmbeddingBag(9, 4, mode="sum")
    bag.weight = jnp.asarray(w)
    out = np.asarray(bag.update_output(jnp.asarray(idx[:, 0])))
    np.testing.assert_allclose(out, w[idx[:, 0]], rtol=1e-6)


# -- guardrails and knobs ----------------------------------------------------
def test_auto_density_rule_keeps_long_sequences_dense():
    """lstm_text's regime: lookups >> vocab -> the auto rule stays
    dense (docs/sparse.md 'when dense wins'); sparse=True forces."""
    lt = nn.LookupTable(100, 4)
    assert lt._sparse_active(49, 100)          # 2*49 <= 100
    assert not lt._sparse_active(51, 100)      # past half the table
    assert nn.LookupTable(100, 4, sparse=True)._sparse_active(5000, 100)
    # and end to end: a batch touching most of the vocab never engages
    fn = lambda: _classifier(vocab=16)  # noqa: E731
    _, _, st = _train(fn, "auto", steps=1,
                      batch=_batch(vocab=16, batch=16, seq=4))
    assert st._sparse_stats is None


def test_off_knob_and_guardrails_force_dense():
    fn = lambda: _classifier()  # noqa: E731
    _, _, st = _train(fn, "off", steps=1)
    assert st._sparse_stats is None
    # max_norm renorm is differentiated through on the dense path only
    assert not nn.LookupTable(V, D, max_norm=1.0)._sparse_active(4, V)
    # a regularized table's reg gradient is dense by definition
    from bigdl_tpu.optim.regularizer import L2Regularizer

    reg_fn = lambda: _classifier(w_regularizer=L2Regularizer(1e-3))  # noqa: E731
    dense, _, _ = _train(reg_fn, "off", steps=3)
    sparse, _, st = _train(reg_fn, "on", steps=3)
    assert st._sparse_stats is None  # table excluded -> no sparse leg
    _assert_params_close(dense, sparse)


def test_value_clipping_outside_zero_disables_sparse():
    fn = lambda: _classifier()  # noqa: E731
    _cfg("on")
    x, y = _batch()
    st = TrainStep(fn(), nn.ClassNLLCriterion(), optim.SGD(0.1),
                   gradient_clipping=(0.01, 1.0))
    assert st._sparse_tables == {}
    st2 = TrainStep(fn(), nn.ClassNLLCriterion(), optim.SGD(0.1),
                    gradient_clipping=(-1.0, 1.0))
    assert st2._sparse_tables  # zero-preserving bounds keep the path
    dense, _, _ = _train(fn, "off", gradient_clipping=(-0.02, 0.02))
    sparse, _, _ = _train(fn, "on", gradient_clipping=(-0.02, 0.02))
    _assert_params_close(dense, sparse)


def test_multi_call_table_densifies_before_nonlinear_legs():
    """A table used twice per forward (overlapping index sets) must see
    value clipping / compression applied to the cross-call SUM, exactly
    like the dense path — the per-call-then-sum ordering diverges by up
    to the whole clip budget on overlapping rows (review finding)."""
    from bigdl_tpu.nn.module import Module

    class DoubleLookup(Module):
        def __init__(self):
            super().__init__()
            self.emb = nn.LookupTable(64, 8, sparse=True)
            self.head = nn.Linear(8, CLASSES)
            self.out = nn.LogSoftMax()

        def update_output(self, x):
            a = jnp.sum(self.emb(x), axis=1)
            b = jnp.sum(self.emb(x[:, ::2]), axis=1)  # overlapping rows
            return self.out(self.head(a + b))

    rng = np.random.RandomState(0)
    x = rng.randint(0, 64, (16, 4)).astype(np.int32)
    y = rng.randint(0, CLASSES, 16)

    def run(mode):
        _cfg(mode)
        RNG.set_seed(0)
        st = TrainStep(DoubleLookup(), nn.ClassNLLCriterion(),
                       optim.SGD(0.5),
                       gradient_clipping=(-1e-4, 1e-4))
        for _ in range(3):
            st.run(x, y, jax.random.key(1))
        return {k: np.asarray(v) for k, v in st.params.items()}

    _assert_params_close(run("off"), run("on"))


def test_duck_typed_optimizer_without_update_mixed_still_trains():
    """The pre-sparse contract: a method implementing only
    init_state()/update() must keep training a sparse-capable model —
    the step densifies the rows locally for it (review finding)."""
    class PlainSGD:
        def init_state(self, params):
            return {"neval": jnp.zeros((), jnp.int32),
                    "epoch": jnp.ones((), jnp.int32)}

        def update(self, grads, params, state):
            new_p = {k: p - 0.1 * grads[k] for k, p in params.items()}
            return new_p, {**state, "neval": state["neval"] + 1}

    _cfg("on")
    x, y = _batch()
    st = TrainStep(_classifier(), nn.ClassNLLCriterion(), PlainSGD())
    before = np.asarray(st.params["0.weight"])
    loss = st.run(x, y, jax.random.key(0))
    assert np.isfinite(loss)
    assert st._sparse_stats  # the capture still engaged
    assert not np.array_equal(before, np.asarray(st.params["0.weight"]))


def test_compression_and_max_norm_ride_the_sparse_rows():
    fn = lambda: _classifier()  # noqa: E731
    for kw in ({"gradient_compression": "bf16"}, {"max_norm": 0.05}):
        dense, _, _ = _train(fn, "off", **kw)
        sparse, _, st = _train(fn, "on", **kw)
        assert st._sparse_stats
        _assert_params_close(dense, sparse)


def test_health_probe_and_grad_fault_see_sparse_grads():
    fn = lambda: _classifier()  # noqa: E731
    _, _, std = _train(fn, "off", steps=1, health_probe=True)
    _, _, sts = _train(fn, "on", steps=1, health_probe=True)
    assert sts._sparse_stats
    np.testing.assert_allclose(np.asarray(sts.last_health),
                               np.asarray(std.last_health), rtol=1e-5)
    # a nan_grads fault poisons the table through the sparse leg too,
    # and skip_nonfinite keeps the previous table wholesale
    _cfg("on")
    x, y = _batch()
    st = TrainStep(fn(), nn.ClassNLLCriterion(), optim.SGD(0.1),
                   grad_fault=True, skip_nonfinite=True)
    before = np.asarray(st.params["0.weight"])
    st.run(x, y, jax.random.key(0), grad_scale=float("nan"))
    after = np.asarray(st.params["0.weight"])
    assert np.isfinite(after).all()
    np.testing.assert_array_equal(before, after)


def test_train_sparse_instant_emitted_and_schema_valid(tmp_path):
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import schema

    _cfg("on")
    x, y = _batch()
    telemetry.start_run(str(tmp_path))
    try:
        st = TrainStep(_classifier(), nn.ClassNLLCriterion(),
                       optim.SGD(0.1))
        st.run(x, y, jax.random.key(0))
    finally:
        telemetry.end_run()
    logs = sorted(tmp_path.glob("*.jsonl"))
    assert logs, list(tmp_path.iterdir())
    events, errors = schema.read_events(str(logs[-1]))
    assert not errors
    assert not schema.validate_events(events)
    inst = [e for e in events
            if e.get("kind") == "event" and e.get("name") == "train/sparse"]
    assert len(inst) == 1
    row = inst[0]
    assert row["tables"] == 1
    assert row["saved_bytes"] > 0
    assert row["dense_bytes"] == V * D * 4
    assert row["rows"][0]["path"] == "0.weight"
    # the stats fold onto /status for tpu_watch's sparse= block
    from bigdl_tpu.telemetry.metrics_http import MetricsSink

    sink = MetricsSink()
    for e in events:
        sink.emit(e)
    assert sink.status()["sparse"]["saved_bytes"] == row["saved_bytes"]


# -- the recsys scenario -----------------------------------------------------
def test_dlrm_registry_model_trains_and_serves_shapes():
    from bigdl_tpu.models import registry

    RNG.set_seed(0)
    model = registry.build_model("dlrm")
    spec = registry.input_spec("dlrm", 4)
    assert tuple(spec.shape) == (4, 21)  # 13 count + 8 categorical
    criterion, tgt = registry.train_pieces("dlrm", 4)
    rng = np.random.RandomState(0)
    x = np.concatenate([rng.randint(0, 100, (4, 13)),
                        rng.randint(0, 50000, (4, 8))],
                       axis=1).astype(np.int32)
    out = model.forward(jnp.asarray(x))
    assert out.shape == (4, 2)
    np.testing.assert_allclose(np.asarray(jnp.exp(out)).sum(axis=1),
                               1.0, rtol=1e-5)
    _cfg("auto")
    st = TrainStep(model, criterion, optim.Adagrad(0.05))
    y = rng.randint(0, 2, 4)
    l0 = st.run(jnp.asarray(x), jnp.asarray(y), jax.random.key(0))
    l1 = st.run(jnp.asarray(x), jnp.asarray(y), jax.random.key(1))
    assert np.isfinite([l0, l1]).all()
    # every table went sparse: 8 bags, 512-row cap each at batch 4*1
    assert st._sparse_stats and st._sparse_stats["tables"] == 8


def test_dlrm_sparse_matches_dense():
    from bigdl_tpu import models

    rng = np.random.RandomState(0)
    x = np.concatenate([rng.randint(0, 100, (8, 13)),
                        rng.randint(0, 300, (8, 8))],
                       axis=1).astype(np.int32)
    y = rng.randint(0, 2, 8)

    def run(mode):
        _cfg(mode)
        RNG.set_seed(0)
        m = models.build_dlrm(vocab_size=300)
        st = TrainStep(m, nn.ClassNLLCriterion(),
                       optim.SGD(0.05, momentum=0.9))
        for i in range(4):
            loss = st.run(jnp.asarray(x), jnp.asarray(y),
                          jax.random.key(5))
        return ({k: np.asarray(v) for k, v in st.params.items()},
                float(loss))

    dense, ld = run("off")
    sparse, ls = run("on")
    assert ld == pytest.approx(ls, rel=1e-6)
    _assert_params_close(dense, sparse)
