"""The ``nemotron_h`` family: its parameter list lines up with the
program's, its parameters and FLOPs are the derivation's, the catalog's
widths are kept and the plan is built from the published pattern's slice,
the scan's FLOP and byte arithmetic and the reader's call counting are
what their docstrings say, the cell's patterns find their events and no
others, and a tiny plan goes through the harness on the CPU in float32
and is judged correct, which the int8 control and a scan that forgets its
carried state are not."""

import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import compare, control, run, trace  # noqa: E402
from benchmark.kernels import ssd  # noqa: E402
from benchmark.models import nemotron_h  # noqa: E402
from benchmark.readers import matched_share, ssd_roofline  # noqa: E402
from rehearse import tiny_cell  # noqa: E402

CONF = run.read_json(run.HERE, "configs", "nemotron_3_super_120b_a12b.json")
CELL = "nemotron_3_super_120b_a12b.train.s8192.b1.c1"


def test_param_specs_line_up_with_the_programs_state_dict():
    from bigdl_tpu.nn.module import state_dict

    conf = tiny_cell("tiny_nemotron_h.c1")["config"]
    own = state_dict(nemotron_h.build(conf), kind="param")
    specs = nemotron_h.param_specs(conf)
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    assert list(own)[:3] == ["0.weight", "1.0.norm2.weight",
                             "1.0.ffn.experts_up"]
    assert [k for k in own if k.startswith("2.0.")][:2] == [
        "2.0.norm1.weight", "2.0.attn.conv_weight"]
    # the published plan, by its specs alone (no 3 GB model is built)
    specs = nemotron_h.param_specs(CONF)
    sizes = {s["name"]: int(np.prod(s["shape"])) for s in specs}
    assert sum(sizes.values()) == CONF["parameters"] == 773582304
    layer = lambda i: sum(v for k, v in sizes.items()  # noqa: E731
                          if k.startswith(f"layer{i}."))
    mixer = 4096 * 4640 + 2048 * 4096 + 2560 * 4 + 2560 + 3 * 32 + 2048
    attn = 2 * 1024 * 4096 + 2 * 128 * 4096
    sparse = 8 * 2 * 1024 * 2688 + 512 + 512 * 4096 + 2 * 4096 * 1024 \
        + 2 * 4096 * 5376
    assert [layer(i) for i in range(11)] == \
        [4096 + sparse, 4096 + mixer] * 5 + [4096 + attn]
    assert sizes["embed"] == sizes["head"] == 16384 * 4096


#: the catalog row's ``config`` (``model-configs`` guide), as published
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}


def test_every_published_width_is_kept_and_the_cut_is_stated():
    differs = sorted(k for k, v in PUBLISHED.items() if CONF[k] != v)
    assert differs == sorted(CONF["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads"])
    assert CONF["published"] == {k: PUBLISHED[k] for k in CONF["reduced"]}
    # each mixer is divided by four, heads and groups (and kv heads) alike
    assert (CONF["mamba_num_heads"] * 4, CONF["n_groups"] * 4,
            CONF["num_attention_heads"] * 4) == (128, 8, 32)
    assert CONF["n_routed_experts_published"] == 512
    assert CONF["held_experts"] == [0, 8] and CONF["first_layer"] == 26
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "64 chips" in CONF["deployment"]
    assert {"no_rotary", "in_proj_order", "groups", "gated_norm", "dt",
            "router", "expert_bias", "latent", "training", "init"} <= set(
        CONF["assumed"])
    assert nemotron_h.layers_of(CONF) == ["sparse", "ssm"] * 5 + ["full"]
    whole = dict(CONF, first_layer=0, num_hidden_layers=88)
    kinds = nemotron_h.layers_of(whole)
    assert (kinds.count("ssm"), kinds.count("sparse"),
            kinds.count("full")) == (40, 40, 8)
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "nemotron_3_super_120b_a12b"]
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]
    assert entry["file"] == "benchmark/configs/nemotron_3_super_120b_a12b.json"


def test_flops_per_record_is_the_derivation():
    f = nemotron_h.flops_per_record(CONF)
    assert f["total"] == CONF["flops_per_record"] == 24976325345280
    mixer = 4096 * 4640 + 2048 * 4096
    attn = 4096 * (1024 + 2 * 128) + 1024 * 4096
    sparse = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        + (22 * 8 / 512) * 2 * 1024 * 2688
    active = 5 * mixer + attn + 5 * sparse + 4096 * 16384
    assert active == 495607808
    assert f["matrix_products"] == 6 * 495607808 * 8192
    assert f["of_which_ssm_projections"] == 6 * 5 * mixer * 8192
    assert f["of_which_expert_layers"] == 6 * 5 * sparse * 8192
    assert f["attention"] == 3 * 4 * 128 * 8 * (8192 * 8193 // 2)
    assert f["ssd"] == 3 * 5 * ssd.flops("fwd", **nemotron_h.ssd_shape(CONF))
    assert f["convolution"] == 6 * 5 * 2560 * 4 * 8192
    for key in ("matrix_products", "attention", "ssd", "convolution"):
        assert str(f[key]) in CONF["flops_derivation"]


def test_ssd_flop_and_byte_arithmetic():
    shape = CONF["ssd_kernel_args"]
    assert shape == {"heads": 32, "groups": 2, "seq": 8192, "head_dim": 64,
                     "state": 128, "chunk": 128, "itemsize": 2, "layers": 5}
    assert {k: shape[k] for k in nemotron_h.ssd_shape(CONF)} == \
        nemotron_h.ssd_shape(CONF)
    q, p, n = 128, 64, 128
    chunk = 2 * 2 * q * q * n + 32 * (2 * q * q * p + 4 * q * p * n)
    assert ssd.chunk_flops(q, 32, 2, p, n) == chunk
    assert ssd.flops("fwd", **shape) == 64 * chunk == 13421772800
    assert ssd.flops("bwd", **shape) == 2 * 64 * chunk
    xy, bc, row = 32 * 8192 * 64 * 2, 2 * 8192 * 128 * 2, 32 * 8192 * 4
    assert ssd.least_bytes("fwd", **shape) == 2 * xy + 2 * bc + row
    assert ssd.least_bytes("bwd", **shape) == 4 * xy + 4 * bc + 2 * row
    # bytes bound the forward (the scan is 0.013 TFLOP a call)
    assert ssd.least_seconds("fwd", 197e12, 819e9, **shape) == \
        (2 * xy + 2 * bc + row) / 819e9


# events of my traced run (PR 40), layouts and all
SCAN_FWD = (
    "%while.287 = (s32[]{:T(128)}, f32[1,2,16,64,128]{4,3,2,1,0:T(8,128)S(1)},"
    " f32[64,1,2,16,64,128]{5,4,3,2,1,0:T(8,128)}, f32[64,1,2,16,1,1]"
    "{3,2,5,4,1,0:T(2,128)}, f32[64,1,2,16]{3,2,1,0:T(2,128)}, /*index=5*/"
    "f32[64,1,2,16,64,128]{5,4,3,0,2,1:T(8,128)}, s32[]{:T(128)}, s32[]"
    "{:T(128)}) while((s32[]{:T(128)}, f32[1,2,16,64,128]{4,3,2,1,0:T(8,128)"
    "S(1)}, f32[64,1,2,16,64,128]{5,4,3,2,1,0:T(8,128)}, f32[64,1,2,16,1,1]"
    "{3,2,5,4,1,0:T(2,128)}, f32[64,1,2,16]{3,2,1,0:T(2,128)}, /*index=5*/"
    "f32[64,1,2,16,64,128]{5,4,3,0,2,1:T(8,128)}, s32[]{:T(128)}, s32[]"
    "{:T(128)}) %tuple.2195), condition=%wide.region_337.437.clone.clone, "
    "body=%wide.region_336.436.clone.clone.sunk")
SCAN_BWD = (
    "%while.293 = (s32[]{:T(128)}, f32[1,2,16,64,128]{4,3,2,1,0:T(8,128)S(1)},"
    " f32[64,1,2,16]{3,2,1,0:T(2,128)}, bf16[64,1,2,16,64,128]{5,4,3,2,1,0:"
    "T(8,128)(2,1)S(1)}, f32[64,1,2,16,64,128]{5,4,3,0,2,1:T(8,128)}, "
    "/*index=5*/f32[64,1,2,16,1,1]{3,2,5,4,1,0:T(2,128)}, f32[64,1,2,16,64,128]"
    "{5,4,3,2,1,0:T(8,128)}, s32[]{:T(128)}, s32[]{:T(128)}, s32[]{:T(128)}) "
    "while((s32[]{:T(128)}, f32[1,2,16,64,128]{4,3,2,1,0:T(8,128)S(1)}) "
    "%tuple.2192), condition=%c, body=%b")
DECAYED = (
    "%convolution_convert_fusion.5 = bf16[2,16,64,128,128]{4,3,2,1,0:T(8,128)"
    "(2,1)} fusion(bf16[1,64,128,2,16,64]{2,1,5,4,3,0:T(8,128)(2,1)} "
    "%bitcast.4015, f32[1,64,128,2,16,64]{2,1,5,4,3,0:T(8,128)S(1)} "
    "%bitcast.3992, f32[2,16,64,128]{3,2,1,0:T(8,128)S(1)} %bitcast.4109), "
    "kind=kOutput, calls=%fused_computation.1093")
CONV = (
    "%multiply_convert_fusion.81 = (bf16[1,8192,2560]{1,2,0}, bf16[1,8192,2560]"
    "{1,2,0}) fusion(f32[1,8192,2560]{1,2,0} %get-tuple-element.8224, "
    "f32[2560]{0} %copy-done.568), kind=kLoop, calls=%fused_computation.7")
NORM = (
    "%fusion.878 = (bf16[1,8192,2048]{1,2,0}, bf16[256,8,64,128]{3,1,2,0}, "
    "bf16[2048]{0}) fusion(f32[1,8192,2048]{1,2,0} %reshape.2865, "
    "bf16[1,8192,4640]{1,2,0} %convolution_bitcast_fusion.2), kind=kLoop, "
    "calls=%fused_computation.9")
# what is NOT the mixer's own: the projections around it, forward, backward
# and the update that holds the weight's gradient product; the routed
# layer; attention
IN_PROJ = (
    "%convolution_bitcast_fusion.9 = bf16[1,8192,4640]{1,2,0:T(8,128)(2,1)"
    "S(1)} fusion(bf16[4640,4096]{1,0:T(8,128)(2,1)} %convert_element_type"
    ".1066, bf16[8192,4096]{0,1:T(8,128)(2,1)} %get-tuple-element.8316, "
    "f32[4096]{0:T(1024)S(1)} %copy-done.868, f32[8192]{0:T(1024)S(1)} "
    "%add_rsqrt_fusion.14), kind=kOutput, calls=%fused_computation.1575")
IN_PROJ_BWD = (
    "%fusion.496 = (bf16[4096]{0:T(1024)(128)(2,1)S(1)}, f32[8192]{0:T(1024)"
    "S(1)}, bf16[8192,4096]{0,1:T(8,128)(2,1)}) fusion(bf16[1,8192,4096]"
    "{1,2,0:T(8,128)(2,1)S(1)} %copy-done.72, f32[8192]{0:T(1024)S(1)} "
    "%copy-done.648, f32[4096]{0:T(1024)S(1)} %copy-done.711, bf16[4640,4096]"
    "{1,0:T(8,128)(2,1)} %convert_element_type.1102, bf16[1,8192,32]{1,2,0:"
    "T(8,128)(2,1)S(1)} %copy-done.421, bf16[1,8192,2560]{1,2,0:T(8,128)(2,1)}"
    " %pad_add_fusion.6, bf16[1,8192,2048]{1,2,0:T(8,128)(2,1)} "
    "%get-tuple-element.8412), kind=kOutput, calls=%fused_computation.960")
IN_PROJ_UPDATE = (
    "%convert_reduce_fusion.52 = (s32[]{:T(128)}, f32[4640,4096]{1,0:T(8,128)}"
    ", f32[4640,4096]{1,0:T(8,128)}, s32[]{:T(128)}, bf16[4640,4096]{1,0:"
    "T(8,128)(2,1)S(1)}) fusion(f32[4640,4096]{1,0:T(8,128)S(1)} "
    "%custom-call.371, f32[]{:T(128)S(6)} %div.334, bf16[1,8192,2560]{1,2,0:"
    "T(8,128)(2,1)} %pad_add_fusion.12), kind=kOutput, calls=%f")
GROUPED = (
    "%ragged-dot-none.25 = bf16[11264,2688]{1,0:T(8,128)(2,1)} custom-call("
    "s32[1]{0:T(128)} %get-tuple-element.2725, bf16[11264,1024]{1,0:T(8,128)"
    "(2,1)} %fusion.430, bf16[8,1024,2688]{2,1,0:T(8,128)(2,1)} %bitcast.4127)"
    ", custom_call_target=\"tpu_custom_call\"")
GROUPED_DW = (
    "%ragged-dot-none.24 = bf16[8,1024,2688]{2,1,0:T(8,128)(2,1)} custom-call("
    "s32[1]{0:T(128)} %get-tuple-element.2721, bf16[11264,1024]{1,0:T(8,128)"
    "(2,1)} %get-tuple-element.1260, bf16[11264,2688]{1,0:T(8,128)(2,1)} "
    "%fusion.429), custom_call_target=\"tpu_custom_call\"")
METADATA = (
    "%ragged-dot-metadata.16 = (s32[9]{0:T(128)S(1)}, s32[29]{0:T(128)S(1)}, "
    "s32[29]{0:T(128)S(1)}, s32[1]{0:T(128)}) custom-call(s32[8]{0:T(128)S(1)}"
    " %bitcast.4280), custom_call_target=\"tpu_custom_call\"")
# the update of an expert stack reads the grouped product's result by name:
# an operand's name is not the instruction's own
EXPERT_UPDATE = (
    "%convert_reduce_fusion.70 = (s32[]{:T(128)}, f32[8,1024,2688]{2,1,0}, "
    "f32[8,1024,2688]{2,1,0}) fusion(f32[8,1024,2688]{2,1,0} %p, "
    "bf16[8,1024,2688]{2,1,0:T(8,128)(2,1)} %ragged-dot-none.24), kind=kLoop")
TOP_K = (
    "%sort.15 = (f32[8192,512]{0,1:T(8,128)}, s32[8192,512]{0,1:T(8,128)S(1)})"
    " sort(f32[8192,512]{0,1:T(8,128)S(1)} %copy.2446, s32[8192,512]{0,1:"
    "T(8,128)S(1)} %custom-call.345), dimensions={1}, is_stable=true")
PICK = (
    "%fusion.86 = f32[180224]{0:T(1024)S(1)} fusion(f32[8192,512]{1,0:T(8,128)"
    "S(1)} %get-tuple-element.8742, s32[180224]{0:T(1024)S(1)} %reshape.3643)"
    ", kind=kCustom, calls=%fused_computation.41.clone")
SHARED = (
    "%fusion.662 = bf16[8192,5376]{1,0} fusion(bf16[5376,4096]{1,0} "
    "%copy-done.284, bf16[8192,4096]{0,1} %get-tuple-element.8274), "
    "kind=kOutput, calls=%fused_computation.12")
ATTN_FWD = (
    "%attn.3 = (bf16[8,8192,128]{2,1,0:T(8,128)(2,1)}, f32[8,8192,1]{2,1,0:"
    "T(8,128)}) custom-call(bf16[8,8192,128]{2,1,0} %bitcast_bitcast_fusion.1,"
    " bf16[1,8192,128]{2,1,0} %get-tuple-element.8522, bf16[1,8192,128]{2,1,0}"
    " %get-tuple-element.8521), custom_call_target=\"tpu_custom_call\"")
ATTN_DQ = (
    "%attn.4 = bf16[8,8192,128]{2,1,0:T(8,128)(2,1)} custom-call("
    "bf16[8,8192,128]{2,1,0} %q), custom_call_target=\"tpu_custom_call\"")
ATTN_DKV = (
    "%attn.5 = (bf16[1,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[1,8192,128]{2,1,0:"
    "T(8,128)(2,1)}) custom-call(bf16[8,8192,128]{2,1,0} %q), "
    "custom_call_target=\"tpu_custom_call\"")


def test_the_cells_patterns_find_their_events_and_no_others():
    scan = CONF["ssd_match"]
    for event in (SCAN_FWD, SCAN_BWD, DECAYED, CONV, NORM):
        assert re.search(scan, event), event
    for event in (IN_PROJ, IN_PROJ_BWD, IN_PROJ_UPDATE, GROUPED, TOP_K,
                  SHARED, ATTN_FWD):
        assert not re.search(scan, event), event
    # the roofline's own match: the scan, not the convolution or the norm
    own = CONF["ssd_scan_match"]
    for event in (SCAN_FWD, SCAN_BWD, DECAYED):
        assert re.search(own, event), event
    for event in (CONV, NORM, IN_PROJ, IN_PROJ_BWD, IN_PROJ_UPDATE, GROUPED,
                  TOP_K, SHARED, ATTN_FWD):
        assert not re.search(own, event), event
    found = lambda event: [k["name"] for k in CONF["ssd_kernels"]  # noqa: E731
                           if re.search(k["match"], event)]
    assert found(SCAN_FWD) == ["ssd.scan"]
    assert found(SCAN_BWD) == ["ssd.scan", "ssd.bwd_scan"]
    assert not found(DECAYED) and not found(CONV) and not found(IN_PROJ)
    routed = CONF["routed_match"]
    for event in (GROUPED, GROUPED_DW, METADATA, TOP_K, PICK):
        assert re.search(routed, event), event
    # the grouped product is found by its OWN name: an update that reads
    # its result is not the routed layer's (PERF.md section 7)
    for event in (EXPERT_UPDATE, SHARED, SCAN_FWD, CONV, IN_PROJ, ATTN_FWD):
        assert not re.search(routed, event), event
    kernels = lambda event: [k["name"] for k in CONF["attention_kernels"]  # noqa: E731
                             if re.search(k["match"], event)]
    assert kernels(ATTN_FWD) == ["attn_mqa.fwd"]
    assert kernels(ATTN_DQ) == ["attn_mqa.dq"]
    assert kernels(ATTN_DKV) == ["attn_mqa.dkv"]
    assert CONF["attention_kernel_args"]["full"] == {
        "heads": 8, "kv_heads": 1, "seq": 8192, "head_dim": 128,
        "window": None, "itemsize": 2, "layers": 1}
    cell = run.load_cell(CELL)
    new = {"kernel.ssd_share", "kernel.ssd_roofline", "moe.latent_share",
           "kernel.attn_mqa_share", "kernel.attn_mqa_roofline"}
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


def test_ssd_roofline_counts_calls_from_the_trace():
    """A layer's step: a forward, the forward again under ``nn.Remat`` and
    a backward, each counted by its scan; the decayed product adds time
    and no call; the convolution adds time to the share alone (the least
    time counts none of its bytes); a projection adds nothing; a call the
    window cuts gives its time and no call."""
    ops = [(SCAN_FWD, 0.0, 0.5), (DECAYED, 0.5, 1.0), (IN_PROJ, 1.0, 2.0),
           (SCAN_FWD, 3.0, 3.5), (CONV, 3.5, 4.0), (SCAN_BWD, 5.0, 6.0),
           (IN_PROJ_UPDATE, 6.0, 9.0)]
    shape = CONF["ssd_kernel_args"]
    least = 2 * ssd.least_seconds("fwd", 197e12, 819e9, **shape) \
        + ssd.least_seconds("bwd", 197e12, 819e9, **shape)
    assert ssd_roofline.read(_ctx(ops)) == pytest.approx(100.0 * least / 2.5)
    cut = ops + [(SCAN_BWD, 9.5, 11.0)]
    assert ssd_roofline.read(_ctx(cut)) == pytest.approx(100.0 * least / 3.0)
    spec = run.read_json(run.HERE, "layer_metrics", "kernel.ssd_share.json")
    assert matched_share.read(_ctx(ops), **spec["args"]) == \
        pytest.approx(100.0 * 3.0 / 7.0)
    assert ssd_roofline.read(_ctx([(IN_PROJ, 1.0, 2.0)])) is None
    # a configuration (or a program) without the scan says nothing
    assert ssd_roofline.read(_ctx(ops, conf={})) is None


def test_a_tiny_nemotron_h_plan_goes_through_the_harness_and_is_correct():
    import jax

    cell = tiny_cell("tiny_nemotron_h.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1


def test_the_control_fails_the_tiny_plans_limits():
    cell = tiny_cell("tiny_nemotron_h.c1")
    nums = control.control_numbers(cell, seed=2 ** 31 + 23)
    assert not compare.judge(nums, cell["workload"]["limits"]), nums
    assert nums["grad1_worst_leaf_gap"] > \
        10 * cell["workload"]["limits"]["grad1_worst_leaf_gap"]


def test_a_scan_that_forgets_its_carried_state_is_not_correct(monkeypatch):
    """What only this family has: a state carried across chunks.  A
    program whose chunks each start from zero runs, trains and is refused
    (the tiny plan's 160 positions are two chunks, and one head in five
    keeps most of its state a token)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import ssd as scan

    real = scan._carry

    def forgetful(decay, own):
        state, entering = real(decay, own)
        return state, jnp.zeros_like(entering)

    monkeypatch.setattr(scan, "_carry", forgetful)
    cell = tiny_cell("tiny_nemotron_h.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is False and out["failed"] == 0
    limits = cell["workload"]["limits"]
    assert out["compared"]["grad1_worst_leaf_gap"] > \
        3 * limits["grad1_worst_leaf_gap"]
