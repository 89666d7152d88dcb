"""The ``xing4`` family: its parameter list lines up with the program's,
its parameters and FLOPs are the derivation's, the catalog's widths are
kept and the cut is stated, the two new kernels' counts are their
derivations, the cell's patterns find their events and no others and its
five metrics read them through their readers, and a tiny plan goes
through the harness on the CPU in float32 and is judged correct, which the
int8 control, one Sinkhorn iteration in place of twenty, a rotary key left
unrotated and a write weight without its factor 2 are not."""

import math
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import compare, control, run, trace  # noqa: E402
from benchmark.kernels import attention, latent_attention, mhc  # noqa: E402
from benchmark.models import xing4  # noqa: E402
from benchmark.readers import (latent_attention_roofline,  # noqa: E402
                               matched_share, mhc_roofline)
from rehearse import tiny_cell  # noqa: E402

CONF = run.read_json(run.HERE, "configs", "xing4_0_29b_a4b.json")
CELL = "xing4_0_29b_a4b.train.s8192.b1.c1"


def test_param_specs_line_up_with_the_programs_state_dict():
    from bigdl_tpu.nn.module import state_dict

    conf = tiny_cell("tiny_xing4.c1")["config"]
    own = state_dict(xing4.build(conf), kind="param")
    specs = xing4.param_specs(conf)
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    # module 1 expands the embedding into the streams and holds nothing
    assert list(own)[:5] == ["0.weight", "2.0.hc_attn.phi",
                             "2.0.hc_attn.bias", "2.0.hc_attn.alpha",
                             "2.0.norm1.weight"]
    assert [s["name"] for s in specs[:5]] == [
        "embed", "layer0.hc_attn.phi", "layer0.hc_attn.b",
        "layer0.hc_attn.a", "layer0.norm1"]
    assert [k for k in own if k.startswith("3.0.ffn.")] == [
        "3.0.ffn.experts_gate", "3.0.ffn.experts_up", "3.0.ffn.experts_down",
        "3.0.ffn.select_bias", "3.0.ffn.router.weight",
        "3.0.ffn.shared.gate_proj.weight", "3.0.ffn.shared.up_proj.weight",
        "3.0.ffn.shared.down_proj.weight"]
    # module 5 sums the streams; the head is its own matrix
    assert list(own)[-2:] == ["6.weight", "7.proj.weight"]
    assert [s["name"] for s in specs[-2:]] == ["norm_f", "head"]
    # the published plan, by its specs alone (no 3 GB model is built)
    specs = xing4.param_specs(CONF)
    sizes = {s["name"]: int(np.prod(s["shape"])) for s in specs}
    assert sum(sizes.values()) == CONF["parameters"] == 759346446
    layer = lambda i: sum(v for k, v in sizes.items()  # noqa: E731
                          if k.startswith(f"layer{i}."))
    attn = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584 \
        + 768 + 512
    path = 24 * 14336 + 24 + 3
    assert (attn, path) == (28411136, 344091)
    dense = attn + 2 * path + 2 * 3584 + 3 * 3584 * 9216
    sparse = attn + 2 * path + 2 * 3584 + 3584 * 64 + 64 \
        + 3 * 3584 * 1024 + 8 * 3 * 3584 * 1024
    assert (dense, sparse) == (128196918, 128426358)
    assert [layer(i) for i in range(5)] == [dense] + [sparse] * 4
    assert sizes["embed"] == sizes["head"] == 16384 * 3584
    for text in ("128196918", "128426358", "759346446", "28411136"):
        assert text in CONF["deployment"], text


#: the catalog row's ``config`` (``model-configs`` guide), as published
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}


def test_every_published_width_is_kept_and_the_cut_is_stated():
    differs = sorted(k for k, v in PUBLISHED.items() if CONF[k] != v)
    assert differs == sorted(CONF["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert CONF["published"] == {k: PUBLISHED[k] for k in CONF["reduced"]}
    assert CONF["num_hidden_layers"] == 5 and CONF["first_layer"] == 1
    assert CONF["held_experts"] == [0, 8] == [0, CONF["n_routed_experts"]]
    assert CONF["n_routed_experts_published"] == 64
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "expert-parallel 8" in CONF["deployment"]
    assert "8 chips share each layer" in CONF["deployment"]
    assert {"multi_token_prediction", "hc_eps", "stream_norm", "clamp",
            "sinkhorn_order", "streams", "phi_order", "coefficients",
            "attention", "router", "expert_bias", "left_out", "training",
            "data", "init"} <= set(CONF["assumed"])
    # the second dense layer, then four expert layers
    assert xing4.layers_of(CONF) == ["dense"] + ["sparse"] * 4
    whole = xing4.layers_of(dict(CONF, num_hidden_layers=40, first_layer=0))
    assert (whole.count("dense"), whole.count("sparse")) == (2, 38)
    assert xing4.qk_dim(CONF) == 192 and xing4.hc_columns(CONF) == 24
    assert xing4.softmax_scale(CONF) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert xing4._rotary_conf(CONF)["attention_factor"] == 1.0
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "xing4_0_29b_a4b"]
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]
    assert entry["file"] == "benchmark/configs/xing4_0_29b_a4b.json"
    assert bench["configs"][-1] is entry
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "kernel.attn_mla_share", "kernel.attn_mla_roofline",
        "kernel.mhc_share", "kernel.mhc_roofline", "moe.routed64x8_share"]


def test_flops_per_record_and_the_two_byte_counts_are_their_derivations():
    f = xing4.flops_per_record(CONF)
    assert f["total"] == CONF["flops_per_record"] == 28509103718400
    attn = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    expert = 3 * 3584 * 1024
    sparse = 3584 * 64 + expert + expert // 2   # 4 x 8 / 64 of an expert
    active = 5 * attn + 3 * 3584 * 9216 + 4 * sparse + 3584 * 16384
    assert active == 366837760
    assert f["matrix_products"] == 6 * active * 8192
    assert f["residual_path"] == 6 * 10 * 24 * 14336 * 8192
    kept = 8192 * 8193 // 2
    assert attention.kept_elements(8192) == kept == 33558528
    assert f["attention"] == 5 * 3 * 2 * (192 + 128) * 32 * kept
    assert 0.36 < f["attention"] / f["total"] < 0.365
    for key in ("matrix_products", "residual_path", "attention"):
        assert str(f[key]) in CONF["flops_derivation"], key
    shape = xing4.attention_shape(CONF)
    args = CONF["latent_attention_kernel_args"]
    assert shape == {k: args[k] for k in shape}
    assert args == {"heads": 32, "seq": 8192, "qk_dim": 192,
                    "value_dim": 128, "itemsize": 2, "layers": 5}
    # each product over its own width: q k^T, ds k, ds^T q over 192; p v,
    # do v^T, p^T do over 128
    per = lambda qk, v: 2 * (qk * 192 + v * 128) * 32 * kept  # noqa: E731
    assert latent_attention.flops("fwd", **args) == per(1, 1)
    assert latent_attention.flops("dq", **args) == per(2, 1)
    assert latent_attention.flops("dkv", **args) == per(2, 2)
    # at equal widths it is the accepted count
    same = dict(heads=32, seq=8192, qk_dim=128, value_dim=128, itemsize=2)
    old = dict(heads=32, kv_heads=32, seq=8192, head_dim=128, itemsize=2)
    for d in ("fwd", "dq", "dkv"):
        assert latent_attention.flops(d, **same) == attention.flops(d, **old)
        assert latent_attention.least_bytes(d, **same) == \
            attention.least_bytes(d, **old)
    qk, v, row = 32 * 8192 * 192 * 2, 32 * 8192 * 128 * 2, 32 * 8192 * 4
    assert latent_attention.least_bytes("fwd", **args) == 2 * qk + 2 * v + row
    assert latent_attention.least_bytes("dkv", **args) == \
        3 * qk + 3 * v + 2 * row
    # the products bound every call at this length, not the bytes
    for d in ("fwd", "dq", "dkv"):
        assert latent_attention.flops(d, **args) / 197e12 > \
            5 * latent_attention.least_bytes(d, **args) / 819e9
    # the residual path: streams read once and written once, u written and
    # f read: ten arrays of [8192, 3584] bfloat16 a forward, 0.59 GB
    path = CONF["mhc_kernel_args"]
    assert path == {"tokens": 8192, "streams": 4, "channels": 3584,
                    "itemsize": 2, "write": "in_product", "sublayers": 10}
    whole = dict(path, write="whole")
    one = 8192 * 3584 * 2
    small = 8192 * 24 * 4 + 24 * 14336 * 2
    assert mhc.least_bytes("fwd", **whole) == 10 * one + small
    assert mhc.least_bytes("bwd", **whole) == 15 * one + 2 * small
    assert 0.587e9 < mhc.least_bytes("fwd", **whole) < 0.59e9
    assert small < 0.003 * mhc.least_bytes("fwd", **whole)
    # the forward's write is computed inside the output projection's
    # fusion here: what is left reads the streams once and writes u
    assert mhc.least_bytes("fwd", **path) == 5 * one + small
    assert mhc.least_bytes("bwd", **path) == mhc.least_bytes("bwd", **whole)
    assert mhc.least_seconds("fwd", 819e9, **path) == pytest.approx(
        mhc.least_bytes("fwd", **path) / 819e9)


# events of the step compiled here for a described v5e, layouts and all (a
# device trace names an event by its whole instruction without metadata),
# checked against a dump of a traced run's names on the chip (PERF.md
# section 6, PR 47)
SUMSQ = (
    "%multiply_reduce_fusion.12 = f32[8192]{0:T(1024)} "
    "fusion(bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %get-tuple-"
    "element.7031, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %get-tuple-"
    "element.7032, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-"
    "element.7033, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-"
    "element.7034), kind=kLoop, calls=%fused_computation.712")
PROJ = (
    "%fusion.2099 = f32[24,1,8192]{2,0,1:T(8,128)} "
    "fusion(bf16[24,14336]{1,0:T(8,128)(2,1)} %convert_element_type.1184, "
    "f32[8192]{0:T(1024)} %add_rsqrt_fusion.17, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7022, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7021, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %copy-done.50, "
    "/*index=5*/bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %copy-done.48), "
    "kind=kOutput, calls=%fused_computation.3664")
PROJ_REMAT = (
    "%broadcast_multiply_fusion.9 = (f32[24,1,8192]{2,0,1:T(8,128)S(1)}, "
    "f32[24,1,8192]{2,0,1:T(8,128)}) fusion(f32[8192]{0:T(1024)S(1)} "
    "%fusion.3430, bf16[24,14336]{1,0:T(8,128)(2,1)S(1)} %copy-done.546, "
    "bf16[1,8192,14336]{1,2,0:T(8,128)(2,1)} %pad_maximum_fusion.7), "
    "kind=kOutput, calls=%fused_computation.6188")
SINKHORN = (
    "%multiply_divide_fusion.468 = f32[4,8192]{1,0:T(4,128)} "
    "fusion(f32[4,8192]{1,0:T(4,128)S(1)} %fusion.3638), kind=kLoop, "
    "calls=%fused_computation.6838")
BWD_DX = (
    "%fusion.438 = bf16[1,8192,14336]{1,2,0:T(8,128)(2,1)} "
    "fusion(bf16[24,14336]{1,0:T(8,128)(2,1)} %convert_element_type.1233, "
    "f32[8192]{0:T(1024)} %multiply_multiply_fusion.242, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7142, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7141, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7140, "
    "/*index=5*/bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-"
    "element.6558, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-"
    "element.7077, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-"
    "element.7079, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-"
    "element.7078, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-"
    "element.7076, /*index=10*/bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-"
    "tuple-element.6992, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %copy-"
    "done.23, bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %copy-done.21, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.6991, "
    "f32[4,1,8192]{2,0,1:T(4,128)} %get-tuple-element.7587, "
    "/*index=15*/f32[4,1,8192]{2,0,1:T(4,128)} %broadcast_multiply_fusion.36,"
    " f32[16,1,8192]{2,0,1:T(8,128)} %broadcast_multiply_fusion.18, "
    "f32[8192]{0:T(1024)} %fusion.3431), kind=kOutput, "
    "calls=%fused_computation.1316")
CONCAT = (
    "%pad_maximum_fusion.1 = bf16[1,8192,14336]{1,2,0:T(8,128)(2,1)} "
    "fusion(bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %copy-done.58, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %copy-done.56, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7028, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7027), "
    "kind=kLoop, calls=%fused_computation.1345")
READ_U = (
    "%fusion.576 = (f32[8192]{0:T(1024)}, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)}) "
    "fusion(bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %get-tuple-"
    "element.7030, f32[8192]{0:T(1024)} %bitcast.4872, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %get-tuple-element.7029, "
    "f32[8192]{0:T(1024)} %bitcast.4873, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7028, "
    "/*index=5*/f32[8192]{0:T(1024)} %bitcast.4874, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7027, "
    "f32[8192]{0:T(1024)} %bitcast.4871), kind=kLoop, "
    "calls=%fused_computation.1860")
OUT_PROJ_WRITE = (
    "%fusion.705 = (bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)}, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)}, "
    "bf16[8192,3584]{0,1:T(8,128)(2,1)}) "
    "fusion(bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7027, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.7028, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %copy-done.57, "
    "bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)S(1)} %copy-done.59, "
    "f32[8192]{0:T(1024)} %bitcast.5160, /*index=5*/f32[8192]{0:T(1024)} "
    "%bitcast.5117, f32[8192]{0:T(1024)} %bitcast.5118, f32[8192]{0:T(1024)} "
    "%bitcast.5119, f32[8192]{0:T(1024)} %bitcast.5116, f32[8192]{0:T(1024)} "
    "%bitcast.5161, /*index=10*/f32[8192]{0:T(1024)} %bitcast.5120, "
    "f32[8192]{0:T(1024)} %bitcast.5121, f32[8192]{0:T(1024)} %bitcast.5122, "
    "f32[8192]{0:T(1024)} %bitcast.5123, bf16[3584,4096]{1,0:T(8,128)(2,1)} "
    "%convert_element_type.1147, "
    "/*index=15*/bf16[8192,4096]{0,1:T(8,128)(2,1)} %bitcast.3910), "
    "kind=kOutput, calls=%fused_computation.1989")
QA_PROJ = (
    "%fusion.1708 = bf16[1,8192,1344]{1,2,0:T(8,128)(2,1)S(1)} "
    "fusion(bf16[1,8192,3584]{1,2,0:T(8,128)(2,1)} %get-tuple-element.6755, "
    "f32[3584]{0:T(1024)} %get-tuple-element.6756, f32[8192]{0:T(1024)S(1)} "
    "%add_rsqrt_fusion.22, bf16[576,3584]{1,0:T(8,128)(2,1)} "
    "%convert_element_type.1144, bf16[768,3584]{1,0:T(8,128)(2,1)} "
    "%convert_element_type.1148), kind=kOutput, calls=%fused_computation.3171")
HEAD = (
    "%fusion.4287 = (bf16[8192]{0:T(1024)(128)(2,1)S(1)}, "
    "bf16[8192,16384]{1,0:T(8,128)(2,1)}) "
    "fusion(f32[16384,3584]{1,0:T(8,128)} %params__9_proj_weight__.1, "
    "bf16[8192,3584]{1,0:T(8,128)(2,1)S(1)} %copy.799, "
    "f32[3584]{0:T(1024)S(1)} %copy-done.1154, f32[8192]{0:T(1024)S(1)} "
    "%fusion.3429), kind=kOutput, calls=%fused_computation.7957")
EMBED_GATHER = (
    "%copy.734 = bf16[8192,3584]{0,1:T(8,128)(2,1)S(1)} "
    "copy(bf16[8192,3584]{1,0:T(8,128)(2,1)S(1)} %fusion.38)")
RAGGED = (
    "%ragged-dot-metadata = (s32[9]{0:T(128)}, s32[39]{0:T(128)}, "
    "s32[39]{0:T(128)}, s32[1]{0:T(128)}) custom-call(s32[8]{0:T(128)S(1)} "
    "%bitcast.5179), custom_call_target=\"tpu_custom_call\"")
COND = (
    "%cond.198.clone = (f32[8192,3584]{1,0:T(8,128)}) "
    "conditional(s32[]{:T(128)} %convert_element_type.1338, "
    "(s32[8192,4]{0,1:T(4,128)S(1)}, f32[8192,4]{0,1:T(4,128)S(1)}, "
    "bf16[8192,3584]{0,1:T(8,128)(2,1)S(1)}, "
    "bf16[8,3584,1024]{2,1,0:T(8,128)(2,1)}, "
    "bf16[8,3584,1024]{2,1,0:T(8,128)(2,1)}, "
    "/*index=5*/bf16[8,1024,3584]{2,1,0:T(8,128)(2,1)}) %tuple.2653, "
    "(s32[8192,4]{0,1:T(4,128)S(1)}, f32[8192,4]{0,1:T(4,128)S(1)}, "
    "bf16[8192,3584]{0,1:T(8,128)(2,1)S(1)}, s32[9]{0:T(128)S(1)}, "
    "bf16[8,3584,1024]{2,1,0:T(8,128)(2,1)}, "
    "/*index=5*/bf16[8,3584,1024]{2,1,0:T(8,128)(2,1)}, "
    "bf16[8,1024,3584]{2,1,0:T(8,128)(2,1)}) %tuple.2654), "
    "branch_computations={%region_201.225, %region_205.235}")
ROUTER_SORT = (
    "%sort = (f32[8192,64]{0,1:T(8,128)}, s32[8192,64]{0,1:T(8,128)S(1)}) "
    "sort(f32[8192,64]{0,1:T(8,128)S(1)} %get-tuple-element.7919, "
    "s32[8192,64]{0,1:T(8,128)S(1)} %custom-call.529), dimensions={1}, "
    "is_stable=true, to_apply=%compare-greater-than.1")
ROWS = (
    "%select_add_fusion.4 = bf16[16384,3584]{1,0:T(8,128)(2,1)} "
    "fusion(pred[16384,3584]{1,0:T(8,128)(4,1)S(1)} %custom-call.308, "
    "bf16[16384,3584]{1,0:T(8,128)(2,1)} %ragged-dot-none.12, "
    "pred[16384,3584]{1,0:T(8,128)(4,1)S(1)} %custom-call.308, "
    "bf16[16384,3584]{1,0:T(8,128)(2,1)} %ragged-dot-none.13), kind=kLoop, "
    "calls=%fused_computation.589")
ATTN_FWD = (
    "%mla.15 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, "
    "f32[32,8192,1]{2,1,0:T(8,128)}) custom-"
    "call(bf16[32,8192,192]{2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion, "
    "bf16[32,8192,192]{2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.1, "
    "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)} %get-tuple-element.7128), "
    "custom_call_target=\"tpu_custom_call\"")
ATTN_DQ = (
    "%mla.20 = bf16[32,8192,192]{2,1,0:T(8,128)(2,1)} custom-"
    "call(bf16[32,8192,192]{2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.10, "
    "bf16[32,8192,192]{2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.11, "
    "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)S(1)} %custom-call.409, "
    "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)} %bitcast.5184, "
    "f32[32,8192,1]{2,1,0:T(8,128)} %copy.835, "
    "/*index=5*/f32[32,8192,1]{2,1,0:T(8,128)} %copy.836), "
    "custom_call_target=\"tpu_custom_call\"")
ATTN_DKV = (
    "%mla.21 = (bf16[32,8192,192]{2,1,0:T(8,128)(2,1)}, "
    "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}) custom-"
    "call(bf16[32,8192,192]{2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.10, "
    "bf16[32,8192,192]{2,1,0:T(8,128)(2,1)} %maximum_bitcast_fusion.11, "
    "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)S(1)} %custom-call.409, "
    "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)} %bitcast.5185, "
    "f32[32,8192,1]{2,1,0:T(8,128)} %copy.835, "
    "/*index=5*/f32[32,8192,1]{2,1,0:T(8,128)} %copy.836), "
    "custom_call_target=\"tpu_custom_call\"")

PATH_EVENTS = (SUMSQ, PROJ, PROJ_REMAT, SINKHORN, BWD_DX, CONCAT, READ_U)
ATTENTION_EVENTS = (ATTN_FWD, ATTN_DQ, ATTN_DKV)
ROUTED_EVENTS = (RAGGED, COND, ROUTER_SORT, ROWS)
# a product's fusion is not the path's, though the forward's write is
# computed inside it; nor is the head, whose 16,384 ids are as many as a
# routed layer's capacity has rows, nor the embedding's lookup
OTHERS = (OUT_PROJ_WRITE, QA_PROJ, HEAD, EMBED_GATHER)


def test_the_cells_patterns_find_their_events_and_no_others():
    path, routed = CONF["mhc_match"], CONF["routed_match"]
    for event in PATH_EVENTS:
        assert re.search(path, event), event
    for event in OTHERS + ATTENTION_EVENTS + ROUTED_EVENTS:
        assert not re.search(path, event), event
    for event in ROUTED_EVENTS:
        assert re.search(routed, event), event
    for event in OTHERS + ATTENTION_EVENTS + PATH_EVENTS:
        assert not re.search(routed, event), event
    kernels = lambda key, event: [  # noqa: E731
        k["name"] for k in CONF[key] if re.search(k["match"], event)]
    assert kernels("latent_attention_kernels", ATTN_FWD) == ["attn_mla.fwd"]
    assert kernels("latent_attention_kernels", ATTN_DQ) == ["attn_mla.dq"]
    assert kernels("latent_attention_kernels", ATTN_DKV) == ["attn_mla.dkv"]
    # a pass is counted once: by the projection's product, by its transpose
    assert kernels("mhc_kernels", PROJ) == ["mhc.fwd"]
    assert kernels("mhc_kernels", PROJ_REMAT) == ["mhc.fwd"]
    assert kernels("mhc_kernels", BWD_DX) == ["mhc.bwd"]
    for event in (SUMSQ, SINKHORN, CONCAT, READ_U) + OTHERS:
        assert not kernels("mhc_kernels", event), event
        assert not kernels("latent_attention_kernels", event), event
    # no other cell's attention patterns claim these calls, nor these theirs
    for name in ("laguna_s_2_1", "lfm2_24b_a2b", "granite_4_0_h_micro",
                 "nemotron_3_super_120b_a12b", "qwen3_next_80b_a3b"):
        theirs = run.read_json(run.HERE, "configs", name + ".json")
        for event in ATTENTION_EVENTS:
            assert not [k for k in theirs["attention_kernels"]
                        if re.search(k["match"], event)], (name, event)
    cell = run.load_cell(CELL)
    new = {"kernel.attn_mla_share", "kernel.attn_mla_roofline",
           "kernel.mhc_share", "kernel.mhc_roofline", "moe.routed64x8_share"}
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


def test_the_five_metrics_read_the_trace_through_their_readers():
    """A sub-layer's step as the readers count it: two forward passes of
    the path (the second under ``nn.Remat``) and a backward one, the write
    inside the output projection's fusion in neither the share's time nor
    the least bytes; the three flash calls at their own least times; the
    routed layer's events in their share alone."""
    ops = [(SUMSQ, 0.0, 0.5), (PROJ, 0.5, 1.0), (SINKHORN, 1.0, 1.25),
           (OUT_PROJ_WRITE, 1.25, 2.0), (PROJ_REMAT, 2.0, 2.5),
           (BWD_DX, 2.5, 3.5), (ATTN_FWD, 4.0, 4.5), (ATTN_DQ, 4.5, 5.5),
           (ATTN_DKV, 5.5, 6.5), (COND, 7.0, 8.0), (ROWS, 7.25, 7.5),
           (HEAD, 8.0, 9.0)]
    spec = lambda name: run.read_json(  # noqa: E731
        run.HERE, "layer_metrics", name + ".json")
    busy = 8.0                # 0-3.5, 4-6.5, 7-9
    path = CONF["mhc_kernel_args"]
    least = 2 * mhc.least_seconds("fwd", 819e9, **path) \
        + mhc.least_seconds("bwd", 819e9, **path)
    assert mhc_roofline.read(_ctx(ops), **spec(
        "kernel.mhc_roofline")["args"]) == pytest.approx(100.0 * least / 2.75)
    assert matched_share.read(_ctx(ops), **spec(
        "kernel.mhc_share")["args"]) == pytest.approx(100.0 * 2.75 / busy)
    args = CONF["latent_attention_kernel_args"]
    want = sum(latent_attention.least_seconds(d, 197e12, 819e9, **args)
               for d in ("fwd", "dq", "dkv"))
    assert latent_attention_roofline.read(_ctx(ops), **spec(
        "kernel.attn_mla_roofline")["args"]) == pytest.approx(
        100.0 * want / 2.5)
    assert matched_share.read(_ctx(ops), **spec(
        "kernel.attn_mla_share")["args"]) == pytest.approx(100.0 * 2.5 / busy)
    # the rows' event lies inside the conditional's: counted once
    assert matched_share.read(_ctx(ops), **spec(
        "moe.routed64x8_share")["args"]) == pytest.approx(100.0 * 1.0 / busy)
    # a program without the path or the layer (the parent of PR 47), or a
    # configuration without the keys, says nothing and does not raise
    bare = [(HEAD, 1.0, 2.0), (QA_PROJ, 2.0, 3.0)]
    assert mhc_roofline.read(_ctx(bare)) is None
    assert latent_attention_roofline.read(_ctx(bare)) is None
    assert matched_share.read(_ctx(bare), key="mhc_match") is None
    other = run.read_json(run.HERE, "configs", "laguna_s_2_1.json")
    assert mhc_roofline.read(_ctx(ops, other)) is None
    assert latent_attention_roofline.read(_ctx(ops, other)) is None


def test_a_tiny_xing4_plan_goes_through_the_harness_and_is_correct():
    import jax

    cell = tiny_cell("tiny_xing4.c1")
    out = run.run_cell(cell, 2 ** 31 + 47, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1


def test_the_control_fails_the_tiny_plans_limits():
    cell = tiny_cell("tiny_xing4.c1")
    nums = control.control_numbers(cell, seed=2 ** 31 + 48)
    assert not compare.judge(nums, cell["workload"]["limits"]), nums
    assert nums["grad1_worst_leaf_gap"] > \
        10 * cell["workload"]["limits"]["grad1_worst_leaf_gap"]


def _run_broken(monkeypatch, patch):
    import jax

    patch(monkeypatch)
    cell = tiny_cell("tiny_xing4.c1")
    out = run.run_cell(cell, 2 ** 31 + 47, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is False and out["failed"] == 0
    return out["compared"], cell["workload"]["limits"]


def test_one_sinkhorn_iteration_instead_of_twenty_is_not_correct(monkeypatch):
    """A program whose mixing matrix is normalised once: its rows sum to
    one, its columns do not yet; it runs, trains and is refused."""
    from bigdl_tpu.nn.layers import hyper_connection

    real = hyper_connection.sinkhorn
    got, limits = _run_broken(monkeypatch, lambda m: m.setattr(
        hyper_connection, "sinkhorn", lambda mix, iters: real(mix, 1)))
    assert got["grad1_worst_leaf_gap"] > 3 * limits["grad1_worst_leaf_gap"]


def test_a_rotary_key_left_unrotated_is_not_correct(monkeypatch):
    """What only latent attention has: ONE rotary key under every head.  A
    program that rotates its queries and forgets the shared key (the only
    array ``Rotary.apply`` sees with one head) is refused."""
    import bigdl_tpu.nn as nn

    real = nn.Rotary.apply

    def forgetful(self, x):
        return x if x.shape[2] == 1 else real(self, x)

    got, limits = _run_broken(
        monkeypatch, lambda m: m.setattr(nn.Rotary, "apply", forgetful))
    assert got["grad1_worst_leaf_gap"] > 3 * limits["grad1_worst_leaf_gap"]


def test_a_write_weight_without_its_factor_two_is_not_correct(monkeypatch):
    from bigdl_tpu.nn.layers import hyper_connection

    got, limits = _run_broken(monkeypatch, lambda m: m.setattr(
        hyper_connection, "POST_GAIN", 1.0))
    assert got["loss1_gap"] > 100 * limits["loss_gap"]
