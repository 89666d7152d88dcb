"""The ``granite_hybrid`` family: its parameter list lines up with the
program's, its parameters and FLOPs are the derivation's, the catalog's
widths are kept and the plan is built from the published ``layer_types``'
slice, the cell's patterns find their events and no others and its four
metrics read them through the accepted readers, and a tiny plan goes
through the harness on the CPU in float32 and is judged correct, which
the int8 control, a scan that forgets its carried state and a head that
forgets to divide its logits are not."""

import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import compare, control, run, trace  # noqa: E402
from benchmark.kernels import attention, ssd  # noqa: E402
from benchmark.models import granite_hybrid  # noqa: E402
from benchmark.readers import (attention_roofline, matched_share,  # noqa: E402
                               ssd_roofline)
from rehearse import tiny_cell  # noqa: E402

CONF = run.read_json(run.HERE, "configs", "granite_4_0_h_micro.json")
CELL = "granite_4_0_h_micro.train.s8192.b1.c1"


def test_param_specs_line_up_with_the_programs_state_dict():
    from bigdl_tpu.nn.module import state_dict

    conf = tiny_cell("tiny_granite_hybrid.c1")["config"]
    own = state_dict(granite_hybrid.build(conf), kind="param")
    specs = granite_hybrid.param_specs(conf)
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    # module 1 is the embedding's multiplier and holds nothing; the head
    # borrows the embedding's matrix and holds nothing either
    assert list(own)[:3] == ["0.weight", "2.0.norm1.weight",
                             "2.0.attn.conv_weight"]
    assert [k for k in own if k.startswith("4.0.")][:2] == [
        "4.0.norm1.weight", "4.0.attn.q_proj.weight"]
    assert list(own)[-1] == "6.weight" and specs[-1]["name"] == "norm_f"
    # the published plan, by its specs alone (no 3 GB model is built)
    specs = granite_hybrid.param_specs(CONF)
    sizes = {s["name"]: int(np.prod(s["shape"])) for s in specs}
    assert sum(sizes.values()) == CONF["parameters"] == 772160448
    layer = lambda i: sum(v for k, v in sizes.items()  # noqa: E731
                          if k.startswith(f"layer{i}."))
    mixer = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    assert (mixer, attn, mlp) == (25847232, 10485760, 50331648)
    mamba, full = 2 * 2048 + mixer + mlp, 2 * 2048 + attn + mlp
    assert (mamba, full) == (76182976, 60821504)
    assert [layer(i) for i in range(10)] == [mamba] * 5 + [full] + [mamba] * 4
    assert sizes["embed"] == 12544 * 2048 and "head" not in sizes


#: the catalog row's ``config`` (``model-configs`` guide), as published
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


def test_every_published_width_is_kept_and_the_cut_is_stated():
    differs = sorted(k for k, v in PUBLISHED.items() if CONF[k] != v)
    assert differs == sorted(CONF["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert CONF["published"] == {k: PUBLISHED[k] for k in CONF["reduced"]}
    assert CONF["num_hidden_layers"] == 10 and CONF["first_layer"] == 0
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "four-stage pipeline" in CONF["deployment"]
    assert "No layer is divided" in CONF["deployment"]
    assert {"in_proj_order", "mlp_order", "gated_norm", "dt", "attention",
            "scalars", "norms", "left_out", "training", "data",
            "init"} <= set(CONF["assumed"])
    kinds = granite_hybrid.layers_of(CONF)
    assert kinds == ["ssm"] * 5 + ["full"] + ["ssm"] * 4
    # any ten consecutive layers are nine to one, the published 36 : 4
    for first in range(31):
        cut = granite_hybrid.layers_of(dict(CONF, first_layer=first))
        assert (cut.count("ssm"), cut.count("full")) == (9, 1)
    whole = granite_hybrid.layers_of(dict(CONF, num_hidden_layers=40))
    assert (whole.count("ssm"), whole.count("full")) == (36, 4)
    assert granite_hybrid.head_dim(CONF) == 64
    assert granite_hybrid.ssm_sizes(CONF) == (64, 64, 1, 128)
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "granite_4_0_h_micro"]
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]
    assert entry["file"] == "benchmark/configs/granite_4_0_h_micro.json"


def test_flops_per_record_is_the_derivation():
    f = granite_hybrid.flops_per_record(CONF)
    assert f["total"] == CONF["flops_per_record"] == 39475061194752
    mixer = 2048 * 8512 + 4096 * 2048
    attn = 2048 * (2048 + 2 * 512) + 2048 * 2048
    mlp = 3 * 2048 * 8192
    active = 9 * mixer + attn + 10 * mlp + 2048 * 12544
    assert active == 771883008
    assert f["matrix_products"] == 6 * active * 8192
    assert f["of_which_ssm_projections"] == 6 * 9 * mixer * 8192
    assert f["of_which_mlp"] == 6 * 10 * mlp * 8192
    assert f["attention"] == 3 * 4 * 64 * 32 * (8192 * 8193 // 2)
    shape = granite_hybrid.ssd_shape(CONF)
    assert shape == {k: CONF["ssd_kernel_args"][k] for k in shape}
    assert CONF["ssd_kernel_args"] == {
        "heads": 64, "groups": 1, "seq": 8192, "head_dim": 64, "state": 128,
        "chunk": 128, "itemsize": 2, "layers": 9}
    # the chunk is the program's (ops.ssd.CHUNK), not the published tiling
    # hint, which nothing reads: at 256 the count would be a third higher
    from bigdl_tpu.ops.ssd import CHUNK
    assert shape["chunk"] == CHUNK != CONF["mamba_chunk_size"] == 256
    # C B^T once for all 64 heads
    chunk = 2 * 128 * 128 * 128 + 64 * (2 * 128 * 128 * 64
                                        + 4 * 128 * 64 * 128)
    assert ssd.flops("fwd", **shape) == 64 * chunk == 26038239232
    assert f["ssd"] == 3 * 9 * 26038239232
    assert f["convolution"] == 6 * 9 * 4352 * 4 * 8192
    for key in ("matrix_products", "of_which_ssm_projections",
                "of_which_mlp", "attention", "ssd", "convolution"):
        assert str(f[key]) in CONF["flops_derivation"], key


# the scan's events of the step compiled here for a described v5e, layouts
# and all, at the chunk the program runs (128: 64 chunks) and at the model's
# published 256 (32 chunks; PR 43 measured both on the chip, and a later PR
# that moves ``ops.ssd.CHUNK`` finds the patterns ready).  DECAY_BWD is the
# backward's decayed product: at 128 neither direction writes a ``Q x Q``
# decay array (the exponential is computed inside the products' fusions), at
# 256 the backward writes one in bfloat16
SCAN_EVENTS = {
    128: {
        "SCAN_FWD": (
            "%while.153 = (s32[]{:T(128)}, f32[1,1,64,64,128]{4,3,2,1,0:T(8,12"
            "8)S(1)}, bf16[64,1,1,64,64,128]{5,4,3,2,1,0:T(8,128)(2,1)S(1)}, f"
            "32[64,1,1,64]{3,2,1,0:T(1,128)}, f32[64,1,1,64,64,128]{5,4,3,2,1,"
            "0:T(8,128)}, /*index=5*/s32[]{:T(128)}, s32[]{:T(128)}) while((s3"
            "2[]{:T(128)}, f32[1,1,64,64,128]{4,3,2,1,0:T(8,128)S(1)}, bf16[64"
            ",1,1,64,64,128]{5,4,3,2,1,0:T(8,128)(2,1)S(1)}, f32[64,1,1,64]{3,"
            "2,1,0:T(1,128)}, f32[64,1,1,64,64,128]{5,4,3,2,1,0:T(8,128)}, /*i"
            "ndex=5*/s32[]{:T(128)}, s32[]{:T(128)}) %tuple.1599), condition=%"
            "wide.region_3.11.clone, body=%wide.region_2.10.clone.sunk"),
        "SCAN_BWD": (
            "%while.171 = (s32[]{:T(128)}, f32[1,1,64,64,128]{4,3,2,1,0:T(8,12"
            "8)S(1)}, f32[64,1,1,64]{3,2,1,0:T(1,128)S(1)}, bf16[64,1,1,64,64,"
            "128]{5,4,3,2,1,0:T(8,128)(2,1)S(1)}, f32[64,1,1,64,64,128]{5,4,3,"
            "2,1,0:T(8,128)}, /*index=5*/f32[64,1,1,64,1,1]{3,5,4,2,1,0:T(1,12"
            "8)}, f32[64,1,1,64,64,128]{5,4,3,2,1,0:T(8,128)}, s32[]{:T(128)},"
            " s32[]{:T(128)}, s32[]{:T(128)}) while((s32[]{:T(128)}, f32[1,1,6"
            "4,64,128]{4,3,2,1,0:T(8,128)S(1)}) %tuple.1), condition=%c, body="
            "%b"),
        "DECAYED": (
            "%convert_bitcast_fusion.70 = f32[1,64,128,1,64,64]{2,5,1,4,3,0:T("
            "8,128)} fusion(f32[1,64,128,1,64,64]{2,1,5,4,3,0:T(8,128)} %bitca"
            "st.1209, f32[64,64,64,128]{3,2,0,1:T(8,128)} %fusion.1260, f32[64"
            ",128,128]{2,1,0:T(8,128)S(1)} %custom-call.209, f32[64,64,128]{2,"
            "1,0:T(8,128)S(1)} %bitcast.5383, pred[128,128]{1,0:T(8,128)(4,1)S"
            "(1)} %copy-done.358, /*index=5*/f32[64]{0:T(128)S(1)} %copy-done."
            "1035, f32[64,64,128]{2,1,0:T(8,128)S(1)} %custom-call.219, f32[64"
            ",64,128]{2,1,0:T(8,128)S(1)} %bitcast.5412), kind=kOutput, calls="
            "%fused_computation.1306"),
        "DECAY_BWD": (
            "%fusion.111 = (f32[64,64,128]{2,1,0:T(8,128)S(1)}, f32[64,64,128]"
            "{2,1,0:T(8,128)S(1)}, bf16[64,128,128]{2,1,0:T(8,128)(2,1)S(1)}) "
            "fusion(f32[64,128,128]{2,1,0:T(8,128)S(1)} %custom-call.86, f32[6"
            "4,64,128]{2,1,0:T(8,128)S(1)} %bitcast.5291, pred[128,128]{1,0:T("
            "8,128)(4,1)S(1)} %copy-done.368, f32[64,64,128]{2,1,0:T(8,128)S(1"
            ")} %bitcast.5326, f32[1,64,128,1,64,64]{2,1,5,4,3,0:T(8,128)} %bi"
            "tcast.1385, /*index=5*/bf16[1,64,128,1,64,64]{2,1,5,4,3,0:T(8,128"
            ")(2,1)S(1)} %bitcast.5238), kind=kOutput, calls=%fused_computatio"
            "n.267"),
    },
    256: {
        "SCAN_FWD": (
            "%while.153 = (s32[]{:T(128)}, f32[1,1,64,64,128]{4,3,2,1,0:T(8,12"
            "8)S(1)}, bf16[32,1,1,64,64,128]{5,4,3,2,1,0:T(8,128)(2,1)S(1)}, f"
            "32[32,1,1,64]{3,2,1,0:T(1,128)}, f32[32,1,1,64,64,128]{5,4,3,2,1,"
            "0:T(8,128)}, /*index=5*/s32[]{:T(128)}, s32[]{:T(128)}) while((s3"
            "2[]{:T(128)}, f32[1,1,64,64,128]{4,3,2,1,0:T(8,128)S(1)}, bf16[32"
            ",1,1,64,64,128]{5,4,3,2,1,0:T(8,128)(2,1)S(1)}, f32[32,1,1,64]{3,"
            "2,1,0:T(1,128)}, f32[32,1,1,64,64,128]{5,4,3,2,1,0:T(8,128)}, /*i"
            "ndex=5*/s32[]{:T(128)}, s32[]{:T(128)}) %tuple.1663), condition=%"
            "wide.region_3.11.clone, body=%wide.region_2.10.clone.sunk"),
        "SCAN_BWD": (
            "%while.171 = (s32[]{:T(128)}, f32[1,1,64,64,128]{4,3,2,1,0:T(8,12"
            "8)S(1)}, f32[32,1,1,64]{3,2,1,0:T(1,128)S(1)}, bf16[32,1,1,64,64,"
            "128]{5,4,3,2,1,0:T(8,128)(2,1)S(1)}, f32[32,1,1,64,64,128]{5,4,3,"
            "2,1,0:T(8,128)}, /*index=5*/f32[32,1,1,64,1,1]{3,5,4,2,1,0:T(1,12"
            "8)}, f32[32,1,1,64,64,128]{5,4,3,2,1,0:T(8,128)}, s32[]{:T(128)},"
            " s32[]{:T(128)}, s32[]{:T(128)}) while((s32[]{:T(128)}, f32[1,1,6"
            "4,64,128]{4,3,2,1,0:T(8,128)S(1)}) %tuple.1), condition=%c, body="
            "%b"),
        "DECAYED": (
            "%convert_bitcast_fusion.53 = f32[1,32,256,1,64,64]{2,5,1,4,3,0:T("
            "8,128)} fusion(f32[1,32,256,1,64,64]{2,1,5,4,3,0:T(8,128)} %bitca"
            "st.1375, f32[32,64,64,256]{3,2,0,1:T(8,128)} %fusion.1582, f32[32"
            ",256,256]{2,1,0:T(8,128)S(1)} %custom-call.155, f32[64,32,256]{2,"
            "1,0:T(8,128)S(1)} %custom-call.212, pred[256,256]{1,0:T(8,128)(4,"
            "1)S(1)} %copy-done.155, /*index=5*/f32[64]{0:T(128)S(1)} %copy-do"
            "ne.1253, f32[64,32,256]{2,1,0:T(8,128)S(1)} %custom-call.280, f32"
            "[64,32,256]{2,1,0:T(8,128)S(1)} %copy_bitcast_fusion.8), kind=kOu"
            "tput, calls=%fused_computation.1397"),
        "DECAY_BWD": (
            "%convolution_convert_fusion.8 = bf16[64,32,256,256]{3,2,1,0:T(8,1"
            "28)(2,1)} fusion(bf16[1,32,256,1,64,64]{2,1,5,4,3,0:T(8,128)(2,1)"
            "S(1)} %bitcast.5179, f32[1,32,256,1,64,64]{2,1,5,4,3,0:T(8,128)} "
            "%bitcast.1506, f32[64,32,256]{2,1,0:T(8,128)S(1)} %custom-call.13"
            "8), kind=kOutput, calls=%fused_computation.211"),
    },
}
CONV = (
    "%multiply_convert_fusion.62 = bf16[1,8192,4352]{1,2,0:T(8,128)(2,1)S(1)} "
    "fusion(bf16[1,8192,4352]{1,2,0:T(8,128)(2,1)} %slice.71, f32[4352]{0:"
    "T(1024)S(1)} %copy-done.811, f32[4352]{0:T(1024)S(1)} %copy-done.800, "
    "f32[4352]{0:T(1024)S(1)} %copy-done.801, f32[4352]{0:T(1024)S(1)} "
    "%copy-done.802, /*index=5*/f32[4352]{0:T(1024)S(1)} %copy-done.799), "
    "kind=kLoop, calls=%fused_computation.818")
NORM_BWD = (
    "%fusion.492 = (bf16[1,8192,4096]{1,2,0:T(8,128)(2,1)}, bf16[1,8192,4096]"
    "{1,2,0:T(8,128)(2,1)}) fusion(f32[1,8192,4096]{1,2,0:T(8,128)} "
    "%reshape.5165, bf16[8192,4096]{0,1:T(8,128)(2,1)S(1)} "
    "%get-tuple-element.4353, f32[4096]{0:T(1024)} %convert_element_type.1571,"
    " f32[8192]{0:T(1024)S(1)} %multiply_multiply_fusion.36, f32[8192]{0:"
    "T(1024)} %fusion.2329, /*index=5*/bf16[1,8192,8512]{1,2,0:T(8,128)(2,1)} "
    "%convolution_bitcast_fusion.8), kind=kLoop, calls=%fused_computation.983")
# what is NOT the mixer's own: the projections around it (the forward's
# gated norm is computed inside the output projection's fusion and goes
# with it), the MLP, attention
IN_PROJ = (
    "%convolution_bitcast_fusion.17 = bf16[1,8192,8512]{1,2,0:T(8,128)(2,1)} "
    "fusion(bf16[8512,2048]{1,0:T(8,128)(2,1)S(1)} %custom-call.146, "
    "f32[2048]{0:T(1024)S(1)} %copy-done.1074, f32[8192]{0:T(1024)S(1)} "
    "%add_rsqrt_fusion.28, bf16[8192,2048]{0,1:T(8,128)(2,1)S(1)} %copy.3458),"
    " kind=kOutput, calls=%fused_computation.881")
OUT_PROJ = (
    "%fusion.950 = (f32[8192]{0:T(1024)S(1)}, bf16[8192,2048]{0,1:T(8,128)"
    "(2,1)}, bf16[8192,2048]{0,1:T(8,128)(2,1)}) fusion(bf16[8192,2048]{0,1:"
    "T(8,128)(2,1)S(1)} %custom-call.56, bf16[2048,4096]{1,0:T(8,128)(2,1)"
    "S(1)} %custom-call.234, f32[1,8192,4096]{1,2,0:T(8,128)} %reshape.5155, "
    "f32[4096]{0:T(1024)S(1)} %copy-done.1032, f32[8192]{0:T(1024)S(1)} "
    "%add_rsqrt_fusion.27, /*index=5*/bf16[1,8192,8512]{1,2,0:T(8,128)(2,1)} "
    "%convolution_bitcast_fusion.17), kind=kOutput, "
    "calls=%fused_computation.1871")
MLP_GATE = (
    "%fusion.1765 = bf16[8192,8192]{1,0:T(8,128)(2,1)} fusion(bf16[8192,2048]"
    "{1,0:T(8,128)(2,1)S(1)} %custom-call.182, bf16[8192,2048]{0,1:T(8,128)"
    "(2,1)} %get-tuple-element.4014, f32[2048]{0:T(1024)S(1)} %copy-done.1073,"
    " f32[8192]{0:T(1024)S(1)} %add_rsqrt_fusion.26), kind=kOutput, "
    "calls=%fused_computation.2706")
ATTN_FWD = (
    "%attn.3 = (bf16[32,8192,64]{2,1,0:T(8,128)(2,1)}, f32[32,8192,1]{2,1,0:"
    "T(8,128)}) custom-call(bf16[32,8192,64]{2,1,0} %bitcast.5238, "
    "bf16[8,8192,64]{2,1,0} %bitcast.5434, bf16[8,8192,64]{2,1,0} "
    "%bitcast.5435), custom_call_target=\"tpu_custom_call\"")
ATTN_DQ = (
    "%attn.4 = bf16[32,8192,64]{2,1,0:T(8,128)(2,1)} custom-call("
    "bf16[32,8192,64]{2,1,0} %bitcast.5236, bf16[8,8192,64]{2,1,0} "
    "%bitcast.5429), custom_call_target=\"tpu_custom_call\"")
ATTN_DKV = (
    "%attn.5 = (bf16[8,8192,64]{2,1,0:T(8,128)(2,1)}, bf16[8,8192,64]{2,1,0:"
    "T(8,128)(2,1)}) custom-call(bf16[32,8192,64]{2,1,0} %bitcast.5235, "
    "bf16[8,8192,64]{2,1,0} %bitcast.5428), "
    "custom_call_target=\"tpu_custom_call\"")
OTHERS = (IN_PROJ, OUT_PROJ, MLP_GATE, ATTN_FWD, ATTN_DQ, ATTN_DKV)


@pytest.mark.parametrize("chunk", sorted(SCAN_EVENTS))
def test_the_scans_patterns_find_their_events_and_no_others(chunk):
    scan = SCAN_EVENTS[chunk]
    own_events = tuple(scan.values())
    whole = CONF["ssd_match"]
    for event in own_events + (CONV, NORM_BWD):
        assert re.search(whole, event), event
    for event in OTHERS:
        assert not re.search(whole, event), event
    # the roofline's own match: the scan, not the convolution or the norm
    own = CONF["ssd_scan_match"]
    for event in own_events:
        assert re.search(own, event), event
    for event in (CONV, NORM_BWD) + OTHERS:
        assert not re.search(own, event), event
    found = lambda event: [k["name"] for k in CONF["ssd_kernels"]  # noqa: E731
                           if re.search(k["match"], event)]
    assert found(scan["SCAN_FWD"]) == ["ssd_whole.scan"]
    assert found(scan["SCAN_BWD"]) == ["ssd_whole.scan", "ssd_whole.bwd_scan"]
    assert not found(scan["DECAYED"]) and not found(CONV)
    assert not found(IN_PROJ)
    # Nemotron's patterns are on its own shapes: neither cell's scan is the
    # other's
    theirs = run.read_json(run.HERE, "configs",
                           "nemotron_3_super_120b_a12b.json")
    for event in own_events:
        assert not re.search(theirs["ssd_scan_match"], event)


def test_the_cells_patterns_find_their_events_and_no_others():
    kernels = lambda event: [k["name"] for k in CONF["attention_kernels"]  # noqa: E731
                             if re.search(k["match"], event)]
    assert kernels(ATTN_FWD) == ["attn_nope.fwd"]
    assert kernels(ATTN_DQ) == ["attn_nope.dq"]
    assert kernels(ATTN_DKV) == ["attn_nope.dkv"]
    assert CONF["attention_kernel_args"]["full"] == {
        "heads": 32, "kv_heads": 8, "seq": 8192, "head_dim": 64,
        "window": None, "itemsize": 2, "layers": 1}
    cell = run.load_cell(CELL)
    new = {"kernel.ssd_whole_share", "kernel.ssd_whole_roofline",
           "kernel.attn_nope_share", "kernel.attn_nope_roofline"}
    assert {m["name"] for m in cell["per_layer"]} == new | {
        "step.mfu", "step.device_ms", "input.wait_share",
        "input.wait_p90_ms", "dispatch.ms_per_step"}
    assert cell["workload"]["batch"] == 1 and cell["chips"] == 1
    for name in new:
        spec = run.read_json(run.HERE, "layer_metrics", name + ".json")
        assert spec["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            run.HERE, "readers", spec["reader"] + ".py"))


def _ctx(ops, conf=CONF):
    return {"cell": {"config": conf}, "lo": 0.0, "hi": 10.0,
            "device_kind": "TPU v5 lite",
            "peaks": run.read_json(run.HERE, "peaks.json"),
            "trace": trace.Trace([trace.DeviceTrace("d", ops)])}


def test_the_four_metrics_read_the_trace_through_the_accepted_readers():
    """A layer's step as the accepted readers count it: two forward scans
    (the second under ``nn.Remat``) and a backward one, the decayed
    products' time and no call of theirs, the convolution in the share
    and not in the roofline, a projection in neither; the three flash
    calls at their own least times."""
    SCAN_FWD, SCAN_BWD, DECAYED, DECAY_BWD = (
        SCAN_EVENTS[CONF["ssd_kernel_args"]["chunk"]][k]
        for k in ("SCAN_FWD", "SCAN_BWD", "DECAYED", "DECAY_BWD"))
    ops = [(SCAN_FWD, 0.0, 0.5), (DECAYED, 0.5, 1.0), (IN_PROJ, 1.0, 2.0),
           (SCAN_FWD, 3.0, 3.5), (CONV, 3.5, 4.0), (SCAN_BWD, 5.0, 6.0),
           (DECAY_BWD, 6.0, 6.5), (ATTN_FWD, 7.0, 7.5), (ATTN_DQ, 7.5, 8.5),
           (ATTN_DKV, 8.5, 9.0), (MLP_GATE, 9.0, 10.0)]
    shape = CONF["ssd_kernel_args"]
    least = 2 * ssd.least_seconds("fwd", 197e12, 819e9, **shape) \
        + ssd.least_seconds("bwd", 197e12, 819e9, **shape)
    spec = lambda name: run.read_json(  # noqa: E731
        run.HERE, "layer_metrics", name + ".json")
    assert ssd_roofline.read(_ctx(ops), **spec(
        "kernel.ssd_whole_roofline")["args"]) == pytest.approx(
        100.0 * least / 3.0)
    assert matched_share.read(_ctx(ops), **spec(
        "kernel.ssd_whole_share")["args"]) == pytest.approx(100.0 * 3.5 / 7.5)
    assert matched_share.read(_ctx(ops), **spec(
        "kernel.attn_nope_share")["args"]) == pytest.approx(100.0 * 2.0 / 7.5)
    full = CONF["attention_kernel_args"]["full"]
    want = sum(attention.least_seconds(d, 197e12, 819e9, **full)
               for d in ("fwd", "dq", "dkv"))
    assert attention_roofline.read(_ctx(ops), **spec(
        "kernel.attn_nope_roofline")["args"]) == pytest.approx(
        100.0 * want / 2.0)
    # at the program's chunk the bytes bound the whole mixer's scan (0.132
    # ms of products, 0.171 ms of bytes a forward; at 256 they were level)
    flops_s = ssd.flops("fwd", **shape) / 197e12
    bytes_s = ssd.least_bytes("fwd", **shape) / 819e9
    assert 0.7 < flops_s / bytes_s < 0.85
    # a program without the scan (the parent of PR 40) says nothing
    assert ssd_roofline.read(_ctx([(IN_PROJ, 1.0, 2.0)])) is None


def test_a_tiny_granite_hybrid_plan_goes_through_the_harness_and_is_correct():
    import jax

    cell = tiny_cell("tiny_granite_hybrid.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1


def test_the_control_fails_the_tiny_plans_limits():
    cell = tiny_cell("tiny_granite_hybrid.c1")
    nums = control.control_numbers(cell, seed=2 ** 31 + 23)
    assert not compare.judge(nums, cell["workload"]["limits"]), nums
    assert nums["grad1_worst_leaf_gap"] > \
        10 * cell["workload"]["limits"]["grad1_worst_leaf_gap"]


def _run_broken(monkeypatch, patch):
    import jax

    patch(monkeypatch)
    cell = tiny_cell("tiny_granite_hybrid.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is False and out["failed"] == 0
    return out["compared"], cell["workload"]["limits"]


def test_a_scan_that_forgets_its_carried_state_is_not_correct(monkeypatch):
    """The tiny plan's 160 positions are two chunks of the scan's 128: a
    program whose chunks each start from zero runs, trains and is
    refused."""
    import jax.numpy as jnp

    from bigdl_tpu.ops import ssd as scan

    real = scan._carry

    def forgetful(decay, own):
        state, entering = real(decay, own)
        return state, jnp.zeros_like(entering)

    got, limits = _run_broken(
        monkeypatch, lambda m: m.setattr(scan, "_carry", forgetful))
    assert got["grad1_worst_leaf_gap"] > 3 * limits["grad1_worst_leaf_gap"]


def test_a_head_that_forgets_to_divide_its_logits_is_not_correct(monkeypatch):
    """What only this family has: a head whose logits are the tied
    product over ``logits_scaling``.  A program that builds its head
    without the divisor runs, trains and is refused by the first loss."""
    from bigdl_tpu.models import transformer

    real = transformer.VocabHead

    def forgetful(*args, logit_scale=1.0, **kw):
        return real(*args, **kw)

    got, limits = _run_broken(
        monkeypatch, lambda m: m.setattr(transformer, "VocabHead", forgetful))
    assert got["loss1_gap"] > 100 * limits["loss_gap"]
