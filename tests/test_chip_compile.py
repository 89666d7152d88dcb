"""The kernels of the main path, compiled for a described TPU v5e.

No chip is attached here: the installed TPU compiler lowers for a
``v5e:2x2`` topology that is described, not present, and raises what
the chip's compiler would raise (a block shape off the (8, 128) tiling,
a strided vector slice, too much VMEM).  Interpret mode — what every
other kernel test runs — sees none of that.  Nothing executes, so these
cases say nothing about values or times; parity lives in
``test_kernels.py`` and the chip run in ``chip_smoke.py``.

The topology is the session's (``topo`` and ``one_chip`` of
``conftest.py``), described inside a fixture and only there: loading
libtpu at import (or in a ``skipif``/``parametrize`` argument) would
make every pytest worker fight over the library's lock.
``is_tpu_device()`` still sees the CPU here, so each case steers it
with ``as_tpu``.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops import attention, dispatch
from bigdl_tpu.ops.lrn_pallas import cross_map_lrn, within_channel_lrn
from bigdl_tpu.ops.pool_pallas import avg_pool, maxpool_tie_split
from test_kernels import (ONLY_LEG, WHOLE_PLANE, _assert_ragged_blocks,
                          _launches)

pytestmark = pytest.mark.usefixtures(
    "described_compiles_stay_out_of_the_cache")


def _fwd_bwd_text(op, shape, dtype, sharding, n_in=1):
    """Compiled text of ``op``'s value and VJP.  The cotangent is an
    ARGUMENT placed on the described device: a backward whose inputs do
    not depend on such an argument is lowered for the CPU instead."""
    def fwd_bwd(*args):
        *xs, g = args
        y, vjp = jax.vjp(op, *xs)
        return y, vjp(g)

    xs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)] * n_in
    g = jax.ShapeDtypeStruct(jax.eval_shape(op, *xs).shape, dtype,
                             sharding=sharding)
    return jax.jit(fwd_bwd).lower(*xs, g).compile().as_text()


def _backends(op_prefix):
    return {(op, b) for op, b, _ in dispatch.decisions()
            if op.startswith(op_prefix)}


@pytest.mark.parametrize("shape", [(8, 8, 512, 64), (2, 8, 4096, 64)])
def test_flash_attention_compiles(shape, one_chip, as_tpu):
    text = _fwd_bwd_text(
        lambda q, k, v: attention.flash_attention(q, k, v, causal=True),
        shape, jnp.bfloat16, one_chip, n_in=3)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", [(32, 64, 56, 56), (32, 192, 56, 56)])
def test_cross_map_lrn_compiles(shape, dtype, one_chip, as_tpu):
    """The two LRN sites of Inception-v1 (models/inception.py): on one
    chip too the banded product, no Mosaic kernel (PR 44)."""
    text = _fwd_bwd_text(lambda x: cross_map_lrn(x, 5, 1e-4, 0.75, 1.0),
                         shape, dtype, one_chip)
    assert set(dispatch.decisions()) == ONLY_LEG
    assert "tpu_custom_call" not in text
    assert " convolution(" in text


def test_inception_stem_holds_no_kernel_and_no_packed_plane(one_chip,
                                                            as_tpu):
    """Inception-v1 from conv1 to conv2/norm2, forward and backward in
    bf16: both LRN sites between their convolutions.  As a kernel each
    site wanted ``[N, C + 4, 3200]`` operands (56 x 56 padded to 25
    lane tiles), copied in and out of XLA's own layout; none of that may
    come back."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.inception import build_inception_v1
    from bigdl_tpu.nn.module import functional_call, state_dict

    stem = nn.Sequential(*build_inception_v1(1000).get(0).layers[:9])
    assert all(isinstance(stem.get(i), nn.SpatialCrossMapLRN)
               for i in (3, 8))

    def shaped(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    state = jax.tree.map(lambda a: shaped(a.shape), state_dict(stem))

    def fwd_bwd(state, x, g):
        y, vjp = jax.vjp(lambda s, a: functional_call(stem, s, a)[0],
                         state, x)
        return y, vjp(g)

    text = jax.jit(fwd_bwd).lower(state, shaped((32, 3, 224, 224)),
                                  shaped((32, 192, 56, 56))).compile().as_text()
    assert {(d, d.launch["channels"]) for d in dispatch.decisions()
            if d[0].startswith("lrn_cross_map")} \
        == {(d, c) for d in ONLY_LEG for c in (64, 192)}
    assert "tpu_custom_call" not in text
    assert not re.search(r"\[\d+,\d+,3200\]", text)


def test_within_channel_lrn_compiles(one_chip, as_tpu):
    text = _fwd_bwd_text(lambda x: within_channel_lrn(x, 5, 1e-4, 0.75),
                         (8, 32, 32, 32), jnp.float32, one_chip)
    assert "tpu_custom_call" in text


def _pool_window(k, s, pad=((0, 0), (0, 0))):
    return (1, 1, k, k), (1, 1, s, s), ((0, 0), (0, 0)) + tuple(pad)


def _same_pool(x):
    """Inception-v1's 3x3/s1 "same" branch pool: a window that slides
    over its plane, the average pool that stays a plane kernel."""
    dims, strides, pads = _pool_window(3, 1, ((1, 1), (1, 1)))
    return avg_pool(x, dims, strides, pads, pads, True, True)


def test_avg_pool_stride1_compiles(one_chip, as_tpu):
    """A 3x3/s1 branch pool on a 28x28 plane stays a Pallas kernel."""
    text = _fwd_bwd_text(_same_pool, (32, 256, 28, 28), jnp.bfloat16,
                         one_chip)
    assert _backends("pool_avg") == {
        ("pool_avg.fwd", "pallas"), ("pool_avg.bwd", "pallas")}
    assert "tpu_custom_call" in text


def _head(x, w):
    """``relu -> 7x7 head pool -> product``, the pool between the
    neighbours XLA may fuse it into."""
    dims, strides, pads = _pool_window(7, 1)
    y = avg_pool(jax.nn.relu(x), dims, strides, pads, pads, True, True)
    return y.reshape(y.shape[:2]) @ w


@pytest.mark.parametrize("shape", [(256, 1024, 7, 7), (128, 2048, 7, 7)],
                         ids=["inception_v1", "resnet50"])
def test_head_pool_adds_no_instruction_of_its_own(shape, one_chip, as_tpu):
    """The one-chip CNN cells' head pool is a window that is the whole
    plane: a reduction and a broadcast in plain ``jnp``, which XLA
    keeps in its own ``{1,0,3,2}`` layout inside the neighbours'
    fusions.  As a plane kernel it cost two layout copies, a ``pad`` to
    ``[262144,13,13]``, a ``reduce`` over ``[262144,1,1]`` and two
    calls at 4 KB a 98-byte plane (PR 41); none of those may come
    back."""
    def fwd_bwd(x, w, g):
        y, vjp = jax.vjp(_head, x, w)
        return y, vjp(g)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((shape[1], 1000), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((shape[0], 1000), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(fwd_bwd).lower(x, w, g).compile().as_text()
    assert set(dispatch.decisions()) == WHOLE_PLANE
    assert not _launches("pool_avg")
    for banned in ("tpu_custom_call", " pad(", "reduce-window"):
        assert banned not in text, banned
    for ln in text.splitlines():
        assert not re.search(r"= \w+\[262144[,\]]", ln), ln
        assert not re.search(r"= \w+\[[\d,]*7,7\]\S* copy\(", ln), ln


def test_pool_compiles_with_a_ragged_last_block(one_chip, as_tpu):
    """300 planes of 6x6 under a 3x3/s1 window, 256 a grid step: the
    second block is 44 planes and Mosaic has to take it."""
    text = _fwd_bwd_text(_same_pool, (3, 100, 6, 6), jnp.bfloat16, one_chip)
    assert text.count("tpu_custom_call") >= 2
    _assert_ragged_blocks(_launches("pool_avg"))


def _pallas_grids(fn, *args):
    """[(grid, [block shapes])] of every ``pallas_call`` traced in
    ``fn``'s value and VJP."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                found.append((tuple(gm.grid),
                              [tuple(getattr(d, "block_size", d)
                                     for d in b.block_shape)
                               for b in gm.block_mappings]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    def fwd_bwd(x):
        y, vjp = jax.vjp(fn, x)
        return vjp(y)

    walk(jax.make_jaxpr(fwd_bwd)(*args).jaxpr)
    return found


def test_large_planes_keep_one_plane_a_grid_step(as_tpu):
    """What the block rule must leave alone: a plane stack whose planes
    are large (within-channel LRN on 384x384 images) comes out of the
    shared launcher at one plane a step, grid (N*C,), as before."""
    x = jax.ShapeDtypeStruct((2, 3, 384, 384), jnp.float32)
    grids = _pallas_grids(lambda a: within_channel_lrn(a, 5, 1e-4, 0.75), x)
    assert [g for g, _ in grids] == [(6,), (6,)]
    assert {b[0] for _, blocks in grids for b in blocks} == {1}
    assert _launches("lrn_within_channel") == {
        "lrn_within_channel.fwd": {"planes_per_block": 1, "grid": (6,)},
        "lrn_within_channel.bwd": {"planes_per_block": 1, "grid": (6,)}}


@pytest.mark.parametrize("name,shape,make_op", [
    # the aux heads' 5x5/s3 ceil-mode pool (14 -> 4: one overflow row)
    ("pool_avg", (32, 512, 14, 14), lambda: (
        lambda x: avg_pool(x, *_pool_window(5, 3, ((0, 1), (0, 1))),
                           ((0, 0),) * 4, True, True))),
    # the stem's 3x3/s2 ceil-mode max pool under split_ties()
    ("pool_tie_split", (32, 64, 112, 112), lambda: (
        lambda x: maxpool_tie_split(
            x, *_pool_window(3, 2, ((0, 1), (0, 1)))))),
])
def test_strided_pool_compiles_or_takes_xla(name, shape, make_op,
                                            one_chip, as_tpu):
    """Mosaic refuses a strided vector slice, so on a TPU ``auto`` must
    either compile the strided pool or route it to the XLA leg with the
    decision recorded — never hand the chip a kernel it rejects."""
    text = _fwd_bwd_text(make_op(), shape, jnp.bfloat16, one_chip)
    took = {b for _, b in _backends(name)}
    assert took, "no dispatch decision recorded"
    if "pallas" in took:
        assert "tpu_custom_call" in text
    else:
        reasons = {r for op, _, r in dispatch.decisions()
                   if op.startswith(name)}
        assert reasons == {"unsupported-shape"}


def test_partitioned_step_takes_xla_leg(topo, as_tpu):
    """On the four-chip data mesh XLA partitions the step, and the TPU
    compiler refuses a Mosaic kernel there ("cannot be automatically
    partitioned"): inside ``spmd_partitioned`` — the scope TrainStep and
    EvalStep trace under — ``auto`` must take the XLA leg and say so
    (a two-legged op: within-channel LRN)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("data",))
    batch = NamedSharding(mesh, P("data"))

    def fwd_bwd(x, g):
        with dispatch.spmd_partitioned(mesh):
            y, vjp = jax.vjp(lambda a: within_channel_lrn(a, 5, 1e-4, 0.75),
                             x)
            return y, vjp(g)

    x = jax.ShapeDtypeStruct((32, 64, 56, 56), jnp.bfloat16, sharding=batch)
    text = jax.jit(fwd_bwd).lower(x, x).compile().as_text()
    assert "tpu_custom_call" not in text
    assert {(b, r) for _, b, r in dispatch.decisions()} == {
        ("xla", "auto:spmd-partitioned")}
