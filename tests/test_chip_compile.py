"""The kernels of the main path, and the CNN cells' ops that are none,
compiled for a described TPU v5e.

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
from bigdl_tpu.ops.lrn import cross_map_lrn
from bigdl_tpu.ops.pool import avg_pool
from test_kernels import ONLY_LEG, WHOLE_PLANE, _only_leg

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


def _head(x, w):
    """``relu -> 7x7 head pool -> product``, the pool between the
    neighbours XLA may fuse it into."""
    pads = ((0, 0),) * 4
    y = avg_pool(jax.nn.relu(x), (1, 1, 7, 7), (1, 1, 1, 1), pads, pads,
                 True, True)
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
    for banned in ("tpu_custom_call", " pad(", "reduce-window"):
        assert banned not in text, banned
    for ln in text.splitlines():
        assert not re.search(r"= \w+\[262144[,\]]", ln), ln
        assert not re.search(r"= \w+\[[\d,]*7,7\]\S* copy\(", ln), ln


@pytest.mark.parametrize("site", ["aux_head", "branch_pool"])
def test_sliding_average_pools_lower_without_a_kernel(site, one_chip,
                                                      as_tpu):
    """What a TPU off a mesh compiled as plane kernels until PR 46, now
    the one XLA form: an auxiliary head of Inception-v1 (its 5x5/s3
    ceil-mode pool to the classifier) and a 3x3/s1 "same" branch pool,
    forward and backward in bf16.  Lowered text only, no compile."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.inception import _aux_head
    from bigdl_tpu.nn.module import functional_call, state_dict

    block, shape = {
        "aux_head": (_aux_head(512, "loss1", 1000).evaluate(),
                     (32, 512, 14, 14)),
        "branch_pool": (nn.Sequential(
            nn.SpatialAveragePooling(3, 3, 1, 1, 1, 1)), (32, 256, 28, 28)),
    }[site]

    def shaped(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(state, x):
        y, vjp = jax.vjp(lambda s, a: functional_call(block, s, a)[0],
                         state, x)
        return y, vjp(y)

    state = jax.tree.map(lambda a: shaped(a.shape), state_dict(block))
    text = jax.jit(fwd_bwd).lower(state, shaped(shape)).as_text()
    assert set(dispatch.decisions()) == _only_leg("pool_avg")
    assert "reduce_window" in text
    assert "tpu_custom_call" not in text


def test_partitioned_step_takes_xla_leg(topo, as_tpu):
    """On the four-chip data mesh XLA partitions the step, and the TPU
    compiler refuses a Mosaic kernel there ("cannot be automatically
    partitioned"): inside ``spmd_partitioned`` — the scope TrainStep and
    EvalStep trace under — ``auto`` must take the XLA leg and say so
    (a two-legged op: the state-space scan, at shapes its kernels
    take)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.ops.ssd import ssd

    mesh = Mesh(np.array(topo.devices), ("data",))

    def shaped(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    def fwd_bwd(x, dt, a, b, c, d):
        with dispatch.spmd_partitioned(mesh):
            y, vjp = jax.vjp(lambda x: ssd(x, dt, a, b, c, d), x)
            return y, vjp(y)

    f32, bf16 = jnp.float32, jnp.bfloat16
    heads = shaped((16,), f32)
    proj = shaped((4, 256, 1, 128), bf16, "data")
    text = jax.jit(fwd_bwd).lower(
        shaped((4, 256, 16, 64), bf16, "data"),
        shaped((4, 256, 16), f32, "data"), heads, proj, proj,
        heads).compile().as_text()
    assert "tpu_custom_call" not in text
    assert [tuple(d) for d in dispatch.decisions()] == [
        ("ssd", "xla", "auto:spmd-partitioned")]
