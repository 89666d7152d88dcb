"""The windowed, grouped flash-attention kernels at the shapes of the
benchmark's ``laguna_s_2_1`` cell, and the gated delta rule's chunked
scan (the XLA leg, and the Pallas leg's two chunk kernels around the
same scan) and the 256-wide gated attention at those of its
``qwen3_next_80b_a3b`` cell, compiled for a described TPU v5e in
the style of ``test_chip_compile.py`` (the session's ``topo``,
``one_chip`` and ``as_tpu`` of ``conftest.py``):
``[1, 72, 8192, 128]`` queries over 8 kv heads under a 512 window, and
``[1, 48, 8192, 128]`` full.  Nothing executes.  The compiled text, with
operand shapes as a device trace names its events, is also what the
configuration's ``attention_kernels`` patterns have to find.  Last, a
routed layer at the LFM2 and Laguna cells' shapes: which of its two
combines each compiles to, and that ``routed_match`` finds the rows.
"""

import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = {"window": (72, 512), "full": (48, None)}
pytestmark = pytest.mark.usefixtures(
    "described_compiles_stay_out_of_the_cache")


def _traced_text(heads, window, sharding, kv_heads=8, seq=8192, dim=128,
                 batch=1):
    q = jax.ShapeDtypeStruct((batch, heads, seq, dim), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((batch, kv_heads, seq, dim), jnp.bfloat16,
                              sharding=sharding)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: attention.flash_attention(
            *a, causal=True, window=window), q, k, v)
        return out, vjp(do)

    return _as_traced(jax.jit(fwd_bwd).lower(q, kv, kv, q).compile())


def _as_traced(compiled):
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    (module,) = compiled.runtime_executable().hlo_modules()
    return module.to_string(options)


@pytest.mark.parametrize("family", CASES)
def test_cell_attention_kernels_compile_and_are_found(family, one_chip,
                                                      as_tpu):
    heads, window = CASES[family]
    text = _traced_text(heads, window, one_chip)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3          # forward, dq, dkv; k and v never repeated
    assert not re.search(rf"bf16\[{heads},8192,128\]\S* broadcast", text)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_s_2_1.json")) as fh:
        kernels = json.load(fh)["attention_kernels"]
    for k in kernels:
        found = [line for line in calls if re.search(k["match"], line)]
        assert len(found) == (1 if k["family"] == family else 0), k["name"]


def _qwen3_next():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_80b_a3b.json")) as fh:
        return json.load(fh)


def test_gated_256_wide_attention_compiles_and_is_found(one_chip, as_tpu):
    """16 query heads of 256 on 2 kv heads at 16,384 positions: the blocks
    the 128-wide cells run at fit the chip's fast memory at twice the
    head size too."""
    text = _traced_text(16, None, one_chip, kv_heads=2, seq=16384, dim=256)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3
    for k in _qwen3_next()["attention_kernels"]:
        assert len([c for c in calls if re.search(k["match"], c)]) == 1, \
            k["name"]


def _delta_rule_lines(sharding):
    """The rule's value and VJP at the cell's shape, compiled: the lines
    of its text as a trace names them, and what each of the
    configuration's ``delta_kernels`` patterns finds among them."""
    from bigdl_tpu.ops.delta_rule import gated_delta_rule

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def fwd_bwd(q, k, v, g, beta, do):
        out, vjp = jax.vjp(gated_delta_rule, q, k, v, g, beta)
        return out, vjp(do)

    wide = shaped(1, 32, 16384, 128)
    row = shaped(1, 32, 16384, dtype=jnp.float32)
    compiled = jax.jit(fwd_bwd).lower(wide, wide, wide, row, row,
                                      wide).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30
    lines = _as_traced(compiled).splitlines()
    conf = _qwen3_next()
    found = {k["name"]: [ln for ln in lines if re.search(k["match"], ln)]
             for k in conf["delta_kernels"]}
    # a forward and a backward scan over the 256 chunks, told apart
    assert len(found["delta.scan"]) == 2
    assert len(found["delta.bwd_scan"]) == 1
    assert found["delta.bwd_scan"][0] in found["delta.scan"]
    assert all(re.search(conf["delta_match"], ln)
               for ln in found["delta.scan"])
    return lines, found, conf


def test_delta_rule_scan_compiles_and_is_found(one_chip):
    """The chunked scan with its backward at 32 heads of 128 x 128 over
    16,384 positions, XLA's leg (what a mesh runs): it fits, its
    triangular systems are inverted by XLA's own block inversion once a
    forward (and once more where the backward computes the chunks
    again), a forward and a backward scan over the 256 chunks are there,
    and the configuration's patterns find them and tell them apart."""
    from bigdl_tpu.ops import dispatch

    dispatch.clear_decisions()
    lines, _, conf = _delta_rule_lines(one_chip)
    assert [d[1] for d in dispatch.decisions()
            if d[0] == "gated_delta_rule"] == ["xla"]
    assert [ln for ln in lines if "InvertDiagBlocksLowerTriangular" in ln
            and re.search(conf["delta_match"], ln)]


def test_delta_rule_kernels_compile_and_are_read_as_the_rule(one_chip,
                                                             as_tpu):
    """The same on a TPU's own leg: the chunk-local work is two Mosaic
    calls around the same scans.  The benchmark's reading stays in
    place: two scans and one of them the backward's, no call that
    ``delta.call_fwd`` or ``delta.call_bwd`` would count on top of them
    (the kernels return chunked arrays, never ``[.., 32, 16384, 128]``),
    every Mosaic call named by ``delta_match``, and XLA's block inversion
    gone with the systems, which stay in VMEM."""
    from bigdl_tpu.ops import delta_rule, dispatch

    lines, found, conf = _delta_rule_lines(one_chip)
    (said,) = [d for d in dispatch.decisions() if d[0] == "gated_delta_rule"]
    assert tuple(said) == ("gated_delta_rule", "pallas", "auto:tpu")
    assert said.launch["chunks_per_block"] == delta_rule.CHUNK_BLOCK
    assert said.launch["grid"] == (1, 32, 256 // delta_rule.CHUNK_BLOCK)
    assert found["delta.call_fwd"] == [] and found["delta.call_bwd"] == []
    calls = [ln for ln in lines if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert all(re.search(conf["delta_match"], ln) for ln in calls)
    assert not [ln for ln in lines if "InvertDiagBlocksLowerTriangular" in ln]


def _lfm2():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b.json")) as fh:
        return json.load(fh)


def test_64_wide_attention_compiles_and_is_found(one_chip, as_tpu):
    """Two records of 32 query heads of 64 on 8 kv heads at 8,192
    positions: half a lane row a head.  Mosaic takes the blocks the
    128-wide cells run at as they are (a block's last dimension is the
    whole head), k and v are not repeated, and the configuration's
    patterns find the three calls by their flattened ``[64, 8192, 64]``
    and ``[16, 8192, 64]`` results."""
    text = _traced_text(32, None, one_chip, kv_heads=8, seq=8192, dim=64,
                        batch=2)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3
    assert not re.search(r"bf16\[2,32,8192,64\]\S* broadcast", text)
    conf = _lfm2()
    for k in conf["attention_kernels"]:
        assert len([c for c in calls if re.search(k["match"], c)]) == 1, \
            k["name"]
    assert conf["attention_kernel_args"]["full"] == {
        "heads": 2 * 32, "kv_heads": 2 * 8, "seq": 8192, "head_dim": 64,
        "window": None, "itemsize": 2, "layers": 1}
    # the other decoder cells' patterns do not claim these calls
    for other in ("laguna_s_2_1", "qwen3_next_80b_a3b"):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               other + ".json")) as fh:
            theirs = json.load(fh)["attention_kernels"]
        assert not [c for c in calls for k in theirs
                    if re.search(k["match"], c)]


def test_gated_short_conv_compiles_and_is_found(one_chip):
    """The mixer's gates and three-tap convolution, value and VJP, at two
    records of 8,192 positions and 2,048 channels in bfloat16: XLA's
    fusions for the chip.  The configuration's ``shortconv_match`` finds
    what reads the projected ``[2, 8192, 6144]`` or carries the three taps
    and holds no matrix product, its ``shortconv_kernels`` count one
    forward and one backward, and neither claims a projection."""
    from bigdl_tpu.nn.layers.short_conv import causal_depthwise_conv

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def gates(projected, taps):
        gate_in, gate_out, u = jnp.split(projected, 3, axis=-1)
        return gate_out * causal_depthwise_conv(
            gate_in * u, taps).astype(projected.dtype)

    def fwd_bwd(projected, taps, do):
        out, vjp = jax.vjp(gates, projected, taps)
        return out, vjp(do)

    compiled = jax.jit(fwd_bwd).lower(
        shaped(2, 8192, 6144), shaped(2048, 3, dtype=jnp.float32),
        shaped(2, 8192, 2048)).compile()
    lines = [ln for ln in _as_traced(compiled).splitlines() if " fusion(" in ln
             and "ENTRY" not in ln]
    conf = _lfm2()
    reads = [ln for ln in lines
             if re.search(r"fusion\(.*bf16\[2,8192,6144\]", ln)]
    assert len(reads) >= 3 and all(re.search(conf["shortconv_match"], ln)
                                   for ln in reads)
    counted = [k["direction"] for ln in lines
               for k in conf["shortconv_kernels"] if re.search(k["match"], ln)]
    assert sorted(counted) == ["bwd", "fwd"]
    for product in (
            "%fusion.1 = bf16[2,8192,6144]{2,1,0} fusion(bf16[2,8192,2048]"
            "{2,1,0} %x, bf16[6144,2048]{1,0} %w), kind=kOutput",
            "%fusion.2 = (f32[2,8192]{1,0}, bf16[2,8192,2048]{2,1,0}) fusion("
            "bf16[2,8192,2048]{2,1,0} %x, bf16[2048,2048]{1,0} %w, "
            "bf16[2,8192,6144]{2,1,0} %p, f32[2048]{0} %a, f32[2048]{0} %b, "
            "f32[2048]{0} %c), kind=kOutput",
            "%fusion.3 = bf16[2,8192,2048]{2,1,0} fusion(bf16[2,8192,2048]"
            "{2,1,0} %x, f32[2048]{0} %w, f32[2,8192]{1,0} %r), kind=kLoop"):
        assert not re.search(conf["shortconv_match"], product)
        assert not [k for k in conf["shortconv_kernels"]
                    if re.search(k["match"], product)]
    step = {
        "fwd": "%fusion.207 = (f32[]{:T(128)}, bf16[2,8192,2048]{2,1,0:T(8,128)"
               "(2,1)}) fusion(bf16[2,8192,6144]{2,1,0:T(8,128)(2,1)} "
               "%fusion.356), kind=kLoop, calls=%fused_computation.407",
        "again": "%slice_multiply_fusion.20 = bf16[2,8192,2048]{2,1,0:T(8,128)"
                 "(2,1)} fusion(bf16[2,8192,6144]{2,1,0:T(8,128)(2,1)} "
                 "%fusion.412), kind=kLoop, calls=%fused_computation.670",
        "bwd": "%slice_multiply_fusion.19 = (bf16[2,8192,2048]{2,1,0:T(8,128)"
               "(2,1)}, bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)}) fusion("
               "bf16[2,8192,6144]{2,1,0:T(8,128)(2,1)} %fusion.412, "
               "bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} %get-tuple-element.481)"
               ", kind=kLoop, calls=%fused_computation.669"}
    found = {name: [k["direction"] for k in conf["shortconv_kernels"]
                    if re.search(k["match"], event)]
             for name, event in step.items()}
    assert found == {"fwd": ["fwd"], "again": ["fwd"], "bwd": ["bwd"]}
    assert all(re.search(conf["shortconv_match"], e) for e in step.values())


#: a decoder cell's attention layers: configuration, pattern family and
#: the kernels' shape (batch, query heads, kv heads, head size,
#: positions, window)
REMAT_LAYERS = {
    "laguna.full": ("laguna_s_2_1", "full", 1, 48, 8, 128, 8192, None),
    "laguna.window": ("laguna_s_2_1", "window", 1, 72, 8, 128, 8192, 512),
    "qwen3_next.gated": ("qwen3_next_80b_a3b", "full", 1, 16, 2, 256, 16384,
                         None),
    "lfm2.heads64": ("lfm2_24b_a2b", "full", 2, 32, 8, 64, 8192, None),
}


@pytest.mark.parametrize("layer", REMAT_LAYERS)
def test_remat_holds_one_forward_call_the_patterns_find(layer, one_chip,
                                                        as_tpu):
    """The flash kernels at each decoder cell's shape under ``nn.Remat``,
    compiled for the chip: the backward pass keeps the forward kernel's
    output and logsumexp, so the gradient holds ONE forward call beside
    dq and dk/dv (a policy that keeps nothing holds two:
    ``tests/test_attention.py`` counts both in whole blocks), each still
    found by the configuration's ``attention_kernels`` pattern (operand
    and result shapes are what they were: the benchmark's readers keep
    reading them)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn.module import functional_call

    config, family, batch, heads, kv_heads, dim, seq, window = \
        REMAT_LAYERS[layer]

    class Flash(nn.Module):
        def update_output(self, qkv):
            return attention.flash_attention(*qkv, causal=True,
                                             window=window)

    model = nn.Remat(Flash())

    def loss(*qkv):
        return jnp.sum(functional_call(model, {}, list(qkv))[0]
                       .astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((batch, heads, seq, dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((batch, kv_heads, seq, dim), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    calls = [line for line in _as_traced(compiled).splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 3
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as fh:
        kernels = json.load(fh)["attention_kernels"]
    found = {k["direction"]: len([c for c in calls
                                  if re.search(k["match"], c)])
             for k in kernels if k["family"] == family}
    assert found == {"fwd": 1, "dq": 1, "dkv": 1}


#: a decoder cell's routed layer: configuration, tokens a step, the
#: layer's arguments, the combine its shapes choose
ROUTED_LAYERS = {
    "lfm2.whole-order": ("lfm2_24b_a2b", 16384, dict(
        d_model=2048, width=1536, n_experts=64, top_k=4, held=(0, 16),
        score="sigmoid", select_bias=True), "fold"),
    "laguna.prefix": ("laguna_s_2_1", 8192, dict(
        d_model=3072, width=1024, n_experts=256, top_k=10, held=(0, 8),
        routed_scale=2.5), "scatter_add"),
}


@pytest.mark.parametrize("case", ROUTED_LAYERS)
def test_routed_layer_combines_by_its_shapes(case, one_chip, monkeypatch):
    """``nn.RoutedExperts`` forward and backward in bfloat16, for the
    chip.  Compiled at LFM2's shapes, the sorted order is whole (65,536 =
    4 x 16,384 rows): no scatter touches a ``[65536, *]`` row array, the
    two gathers by the order's inverse and the two sums over ``top_k``
    are there, and the configuration's ``routed_match`` finds every
    instruction that holds the 65,536 rows in ANY view (a ``[16384, 4,
    2048]`` one would fall out of ``moe.routed64_share``).  Lowered at
    Laguna's (10,240 of 81,920 assignments), the ``conditional`` and its
    float32 scatter-add of the capacity's rows are still there."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import telemetry
    from bigdl_tpu.nn import init
    from bigdl_tpu.nn.module import functional_call, state_dict

    config, tokens, args, combine = ROUTED_LAYERS[case]
    said = []
    monkeypatch.setattr(telemetry, "instant",
                        lambda name, **attrs: said.append((name, attrs)))
    # the stacks' values do not reach a compile: zeros, not 600 MB of draws
    monkeypatch.setattr(init.RandomUniform, "init",
                        lambda self, shape, **_: np.zeros(shape, np.float32))
    layer = nn.RoutedExperts(**args)
    buffers = state_dict(layer, kind="buffer")

    def loss(params, x):
        y, _ = functional_call(layer, {**params, **buffers}, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def shaped(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    params = {k: shaped(v.shape)
              for k, v in state_dict(layer, kind="param").items()}
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, shaped((tokens, args["d_model"])))
    (route,) = [attrs for name, attrs in said if name == "moe/route"]
    assert route["combine"] == combine
    rows, d = route["capacity"], args["d_model"]
    if combine == "scatter_add":
        # what stays as it was is read off the program as it is handed to
        # the compiler: compiling the sort of the assignments takes 20 s
        text = lowered.as_text(dialect="hlo")
        assert rows < tokens * args["top_k"]
        assert " conditional(" in text and f"f32[{rows},{d}]" in text
        assert re.search(rf"= f32\[{tokens},{d}\]\S* scatter\(", text)
        return
    text = _as_traced(lowered.compile())
    scatters = [ln for ln in text.splitlines()
                if re.search(rf" scatter\(.*\[{rows},\d\d+\]", ln)]
    assert rows == tokens * args["top_k"] == 65536
    assert not scatters and " conditional(" not in text
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as fh:
        routed_match = json.load(fh)["routed_match"]
    entry = text[text.index("\nENTRY "):].splitlines()
    wide = {rows * d, rows * args["width"]}
    held = [ln for ln in entry if any(
        math.prod(int(n) for n in dims.split(",")) in wide
        for dims in re.findall(r"\[([\d,]+)\]", ln))]
    assert len(held) > 20
    assert all(re.search(routed_match, ln) for ln in held)
    # rows read by the inverse: [65536, d] in, [65536, d] out, an index a row
    gathers = [ln for ln in held if re.search(
        rf"= bf16\[{rows},{d}\]\S* fusion\(bf16\[{rows},{d}\]\S* %\S+, "
        rf"s32\[{rows}\]\S* %\S+\), kind=kCustom", ln)]
    sums = [ln for ln in held if re.search(
        rf"= \w+\[{tokens},{d}\]\S* fusion\(.*bf16\[{rows},{d}\]", ln)]
    assert len(gathers) == 2 and len(sums) == 2


def _granite_mamba_block(one_chip, monkeypatch):
    """A ``mamba`` layer of the ``granite_4_0_h_micro`` plan at its
    published widths, as ``(configuration, gradient of a loss by the
    parameters and the input, their shapes in bfloat16 at 8,192
    positions)``."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn import init
    from bigdl_tpu.nn.module import functional_call, state_dict

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite_4_0_h_micro.json")) as fh:
        conf = json.load(fh)
    d, heads, p = conf["hidden_size"], conf["mamba_n_heads"], \
        conf["mamba_d_head"]
    groups, state = conf["mamba_n_groups"], conf["mamba_d_state"]
    chunk, seq = conf["ssd_kernel_args"]["chunk"], conf["sequence_length"]
    assert (d, heads, p, groups, state, chunk, seq) == (
        2048, 64, 64, 1, 128, 128, 8192)
    # the weights' values do not reach a lowering: zeros, not 300 MB of draws
    monkeypatch.setattr(init.RandomUniform, "init",
                        lambda self, shape, **_: np.zeros(shape, np.float32))
    block = nn.DecoderBlock(
        d, nn.Mamba2Mixer(d, heads, p, groups, state,
                          taps=conf["mamba_d_conv"],
                          eps=conf["rms_norm_eps"]),
        nn.GatedMLP(d, conf["shared_intermediate_size"]),
        eps=conf["rms_norm_eps"],
        residual_scale=conf["residual_multiplier"])
    buffers = state_dict(block, kind="buffer")

    def loss(params, x):
        y, _ = functional_call(block, {**params, **buffers}, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def shaped(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    params = {k: shaped(v.shape)
              for k, v in state_dict(block, kind="param").items()}
    assert params["attn.in_proj.weight"].shape == (8512, 2048)
    assert params["attn.conv_weight"].shape == (4352, 4)
    return conf, jax.jit(jax.grad(loss, argnums=(0, 1))), (
        params, shaped((1, seq, d)))


def test_whole_mamba2_block_lowers_for_the_chip(one_chip, monkeypatch):
    """A ``mamba`` layer of the ``granite_4_0_h_micro`` plan at its
    published widths (hidden 2048; 64 heads of 64 on ONE group, state 128;
    a gated MLP of 8192; what each part adds times 0.22), forward and
    backward in bfloat16 at 8,192 positions, lowered for the chip and read
    as it is handed to the compiler (PR 37): the scan said its own chunk
    (``ops.ssd.CHUNK``; the published ``mamba_chunk_size`` is read by
    nothing) and its one group, the chunked shapes the configuration's
    patterns are written on are the program's (64 chunks of 128: the
    float32 decay exponent ``[1,1,64,64,128,128]``, ``C B^T`` once for all
    64 heads, the carried ``[1,1,64,64,128]`` state), and the residual
    multiplier is in the text."""
    from bigdl_tpu.ops import dispatch

    conf, grad, shapes = _granite_mamba_block(one_chip, monkeypatch)
    chunk = conf["ssd_kernel_args"]["chunk"]
    dispatch.clear_decisions()
    text = grad.lower(*shapes).as_text(dialect="hlo")
    (said,) = [s for s in dispatch.decisions() if s[0] == "ssd"]
    assert said.launch == dict(chunk=chunk, chunks=64, heads=64, head_dim=64,
                               state=128, groups=1)
    for shape in ("f32[1,1,64,64,128,128]", "f32[1,1,64,128,128]",
                  "f32[1,1,64,64,128]", "f32[64,1,1,64,64,128]",
                  "bf16[1,8192,8512]", "bf16[1,8192,4352]"):
        assert shape in text, shape
    assert "f32[1,1,64,32,256,256]" not in text      # no chunk of 256
    # 0.22 as bfloat16 holds it: the multiply is in the compute type
    assert re.search(r"bf16\[\] constant\(0\.2197\)", text)
    scan_match = conf["ssd_scan_match"]
    for line in ("%f = f32[64,64,128]{2,1,0} fusion(f32[1,1,64,64,128]{4,3,2,"
                 "1,0} %a), kind=kLoop",
                 "%w = (s32[], f32[1,1,64,64,128]{4,3,2,1,0}, "
                 "f32[64,1,1,64,64,128]{5,4,3,2,1,0}) while((s32[]) %t)"):
        assert re.search(scan_match, line)
    # a projection's product is not the scan's, whatever its other operand
    assert not re.search(scan_match, "%p = bf16[1,8192,8512]{2,1,0} fusion("
                         "bf16[8512,2048]{1,0} %w, bf16[8192,2048]{1,0} %x)")


def _scan_events(text, confs):
    """Of a compiled text: its Mosaic calls, and of its ``while``s the
    ``ssd_kernels`` names of any of ``confs`` that would count it."""
    lines = text.splitlines()
    calls = [ln for ln in lines if "tpu_custom_call" in ln]
    whiles = [sorted(k["counts"] for conf in confs
                     for k in conf["ssd_kernels"] if re.search(k["match"], ln))
              for ln in lines if " while(" in ln]
    return calls, sorted(w for w in whiles if w)


SCAN_CELLS = {"granite-block": "granite_4_0_h_micro",
              "nemotron-scan": "nemotron_3_super_120b_a12b"}


@pytest.mark.parametrize("config", SCAN_CELLS.values(), ids=SCAN_CELLS)
def test_ssd_kernels_compile_and_are_read_as_the_scan(config, one_chip,
                                                      as_tpu, monkeypatch):
    """The scan on a TPU's own leg: ONE Mosaic call a direction that walks
    the chunks in order with the state in VMEM (Mosaic takes the resident
    state, the scratch and the backward's reversed index maps), in the
    whole Granite block (64 heads on one group) and in a scan of the
    Nemotron cell's shape (32 heads on 2 groups).  No ``while`` of the
    scan is left for either configuration's ``ssd_kernels`` to count (the
    two scan rooflines read nothing until a ``benchmark`` PR re-points
    them), nothing fills a stack of states with zeros, and the shares keep
    reading: both calls are named by the configuration's ``ssd_scan_match``
    and ``ssd_match`` (each holds the ``[chunks, batch, groups, heads a
    group, head_dim, state]`` stack of entering states) and by neither
    pattern of the other cell; no ``Q x Q`` decay of all chunks is in the
    text."""
    from bigdl_tpu.ops import dispatch, ssd

    confs = {}
    for name in SCAN_CELLS.values():
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as fh:
            confs[name] = json.load(fh)
    conf = confs[config]
    if config == "granite_4_0_h_micro":
        _, grad, shapes = _granite_mamba_block(one_chip, monkeypatch)
    else:
        def shaped(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def loss(*args):
            y, state = ssd.ssd(*args, return_state=True)
            return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(state)

        f32 = jnp.float32
        grad = jax.jit(jax.grad(loss, argnums=tuple(range(6))))
        shapes = (shaped(1, 8192, 32, 64), shaped(1, 8192, 32, dtype=f32),
                  shaped(32, dtype=f32), shaped(1, 8192, 2, 128),
                  shaped(1, 8192, 2, 128), shaped(32, dtype=f32))
    args = conf["ssd_kernel_args"]
    heads, groups = args["heads"], args["groups"]
    dispatch.clear_decisions()
    text = _as_traced(grad.lower(*shapes).compile())
    (said,) = [s for s in dispatch.decisions() if s[0] == "ssd"]
    assert tuple(said) == ("ssd", "pallas", "auto:tpu")
    assert ssd.CHUNK == args["chunk"] == 128
    assert said.launch == dict(
        chunk=128, chunks=64, heads=heads, head_dim=64, state=128,
        groups=groups, head_block=ssd.HEAD_BLOCK,
        grid=(1, 64, heads // ssd.HEAD_BLOCK),
        carry="vmem", calls=2, state_bytes=heads * 64 * 128 * 4)
    assert said.launch["state_bytes"] <= ssd.STATE_BUDGET
    calls, whiles = _scan_events(text, confs.values())
    # a forward and a backward, and nothing of the scan between them
    assert len(calls) == 2
    assert whiles == []
    r = heads // groups
    stack = rf"f32\[64,1,{groups},{r},64,128\]"
    assert all(re.search(stack, ln) for ln in calls)
    assert not re.search(stack + r"\S* broadcast\(", text)
    for pattern in (conf["ssd_scan_match"], conf["ssd_match"]):
        assert all(re.search(pattern, ln.strip()) for ln in calls)
    assert f"f32[1,{groups},{r},64,128,128]" not in text
    assert not re.search(r"f32\[[\d,]*128,128,128\]", text)
    # the other state-space cell's patterns do not claim these calls
    theirs = next(c for name, c in confs.items() if name != config)
    for pattern in (theirs["ssd_scan_match"], theirs["ssd_match"]):
        assert not [ln for ln in calls if re.search(pattern, ln.strip())]


# -- the flash kernels' value width, and the plan that needs it ---------------

def _masked_sha(text):
    """sha256 of a lowered text with its Mosaic payload bodies masked
    (they carry the line numbers of the kernel's call stack)."""
    import hashlib

    masked = re.sub(r'\\22body\\22: \\22.*?\\22', "", text)
    return hashlib.sha256(masked.encode()).hexdigest()


def _gqa_block_text(one_chip):
    """A one-stream decoder block of grouped-query attention (12 heads on
    4 kv heads of 128, a window of 512, rotary, a per-head gate) and a
    gated MLP, forward and backward in bfloat16 at 2,048 positions,
    lowered for the chip."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn.module import functional_call, state_dict

    attn = nn.GroupedQueryAttention(512, 12, 4, 128, window=512,
                                    rotary=nn.Rotary(64), gate="per_head")
    block = nn.DecoderBlock(512, attn, nn.GatedMLP(512, 1024))

    def loss(params, x):
        y, _ = functional_call(block, params, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def shaped(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    params = {k: shaped(v.shape)
              for k, v in state_dict(block, kind="param").items()}
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, shaped((1, 2048, 512))).as_text()


#: ``_masked_sha(_gqa_block_text(...))`` on the tree BEFORE the kernels
#: learned a value width of their own (commit b82440d, PR 46), made by
#: importing this function over that tree's ``bigdl_tpu``
GQA_BLOCK_BEFORE = "76a293969ba45a53d822c23db4815df7f2626f4678b6907d83ac0bba3f1f529c"


def test_at_equal_widths_a_grouped_query_block_lowers_as_it_did(one_chip,
                                                                as_tpu):
    """The kernels take ``v`` of a width of its own; where it is ``q``'s,
    nothing of a block's lowered program moved: every operand type, block
    shape, grid and scratch of the three calls, and everything around
    them, byte for byte outside the Mosaic payloads."""
    text = _gqa_block_text(one_chip)
    assert text.count("tpu_custom_call") == 3
    assert _masked_sha(text) == GQA_BLOCK_BEFORE


def test_xing4_expert_layer_lowers_for_the_chip_with_two_widths(
        one_chip, as_tpu, monkeypatch):
    """An expert layer of the ``xing4_0_29b_a4b`` plan at its published
    widths (hidden 3584 in four streams; 32 latent-attention heads of 128 +
    64 rotary over values of 128 out of latents of 768 and 512; 8 of 64
    experts of 1,024 and a shared one), forward and backward in bfloat16
    at 8,192 positions, lowered for the chip: the three flash calls hold
    queries and keys of 192 and values of 128 as they are (nothing is
    padded to 256), the layer said so, the routed layer runs a prefix of
    16,384 rows and scatter-adds it, and each of the two residual paths
    said its four streams and twenty iterations."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import telemetry
    from bigdl_tpu.nn import init
    from bigdl_tpu.nn.module import functional_call, state_dict
    from bigdl_tpu.ops import dispatch

    sys.path.insert(0, ROOT)
    from benchmark.models import xing4

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_0_29b_a4b.json")) as fh:
        conf = json.load(fh)
    said = []
    monkeypatch.setattr(telemetry, "instant",
                        lambda name, **attrs: said.append((name, attrs)))
    # the values do not reach a lowering: zeros, not 500 MB of draws
    monkeypatch.setattr(init.RandomUniform, "init",
                        lambda self, shape, **_: np.zeros(shape, np.float32))
    # the family's own plan, cut to ONE expert layer and a toy vocabulary
    model = xing4.build(dict(conf, num_hidden_layers=1, first_layer=2,
                             vocab_size=128))
    assert isinstance(model.layers[1], nn.StreamExpand)
    assert isinstance(model.layers[3], nn.StreamSum)
    block = model.layers[2]                              # under nn.Remat
    assert isinstance(block, nn.Remat)
    buffers = state_dict(block, kind="buffer")

    def loss(params, x):
        y, _ = functional_call(block, {**params, **buffers}, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def shaped(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    params = {k: shaped(v.shape)
              for k, v in state_dict(block, kind="param").items()}
    assert sum(math.prod(v.shape) for v in params.values()) == 128426358
    dispatch.clear_decisions()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, shaped((1, 8192, 4 * 3584))).as_text(dialect="hlo")
    (leg,) = [s for s in dispatch.decisions() if s[0] == "latent_attention"]
    assert tuple(leg) == ("latent_attention", "pallas", "auto:tpu")
    assert leg.launch == dict(
        heads=32, qk_dim=192, rope_dim=64, value_dim=128, q_rank=768,
        kv_rank=512, scale=pytest.approx(192 ** -0.5 * (0.1 * math.log(64)
                                                        + 1) ** 2),
        block_q=1024, block_k=512, blocks_visited=72, blocks_total=128)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3                   # the forward is not run again
    assert not [ln for ln in calls if "[32,8192,256]" in ln]
    # out and logsumexp follow v; dq follows q; dk follows q and dv follows v
    results = sorted(re.sub(r"\{[\d,]*\}", "", ln.split(" custom-call(")[0]
                            .split(" = ")[1]) for ln in calls)
    assert results == ["(bf16[32,8192,128], f32[32,8192,1])",
                       "(bf16[32,8192,192], bf16[32,8192,128])",
                       "bf16[32,8192,192]"]
    (route,) = [a for name, a in said if name == "moe/route"]
    assert (route["capacity"], route["worst"], route["combine"],
            route["score"], route["select_bias"], route["shared"]) == (
        16384, 32768, "scatter_add", "sigmoid", True, True)
    paths = [a for name, a in said if name == "residual/mhc"]
    assert len(paths) >= 2 and {(a["streams"], a["sinkhorn_iters"],
                                 a["clamp"], a["dtype"]) for a in paths} == {
        (4, 20, 30.0, "bfloat16")}
    (kept,) = {(a["streams"], tuple(a["shape"]), a["bytes"])
               for name, a in said if name == "remat/keep"
               and a["kept"] == "residual_streams"}
    assert kept == (4, (1, 8192, 14336), 8192 * 14336 * 2)
