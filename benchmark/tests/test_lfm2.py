"""The ``lfm2`` family: its parameter list lines up with the program's,
its parameters and FLOPs are the derivation's, the catalog's widths are
kept and the plan is built from the published ``layer_types`` slice, the
short convolution's byte arithmetic and the reader's call counting are
what their docstrings say, the cell's patterns find their events and no
others, and a tiny plan goes through the harness on the CPU in float32
and is judged correct, which the int8 control and a router that
misplaces its bias are not."""

import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import compare, control, run, trace  # noqa: E402
from benchmark.kernels import short_conv  # noqa: E402
from benchmark.models import lfm2  # noqa: E402
from benchmark.readers import matched_share, shortconv_roofline  # noqa: E402
from rehearse import tiny_cell  # noqa: E402

CONF = run.read_json(run.HERE, "configs", "lfm2_24b_a2b.json")
CELL = "lfm2_24b_a2b.train.s8192.b2.c1"


def test_param_specs_line_up_with_the_programs_state_dict():
    from bigdl_tpu.nn.module import state_dict

    conf = tiny_cell("tiny_lfm2.c1")["config"]
    own = state_dict(lfm2.build(conf), kind="param")
    specs = lfm2.param_specs(conf)
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    # the head adds no leaf: the last one is the final norm's scale
    assert list(own)[0] == "0.weight" and list(own)[-1] == "6.weight"
    assert tuple(own["6.weight"].shape) == (conf["hidden_size"],)
    # the published plan, by its specs alone (no 3 GB model is built)
    specs = lfm2.param_specs(CONF)
    sizes = {s["name"]: int(np.prod(s["shape"])) for s in specs}
    assert sum(sizes.values()) == CONF["parameters"] == 771275136
    layer = lambda i: sum(v for k, v in sizes.items()  # noqa: E731
                          if k.startswith(f"layer{i}."))
    conv = 2048 * 3 + 3 * 2048 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 512 * 2048 + 2 * 64
    sparse = 16 * 3 * 2048 * 1536 + 64 + 64 * 2048
    assert [layer(i) for i in range(5)] == [
        4096 + conv + 3 * 2048 * 11776, 4096 + attn + sparse,
        *[4096 + conv + sparse] * 3]
    assert sizes["embed"] == 8192 * 2048 and "head" not in sizes


def test_the_plan_is_the_published_layer_types_slice():
    assert len(CONF["layer_types"]) == 40
    assert CONF["layer_types"].count("full_attention") == 10
    assert CONF["first_layer"] == 1 and CONF["num_dense_layers"] == 2
    assert [(layer["mixer"], layer["ffn"])
            for layer in lfm2.layers_of(CONF)] == [
        ("conv", "dense"), ("full", "sparse"), ("conv", "sparse"),
        ("conv", "sparse"), ("conv", "sparse")]
    whole = dict(CONF, first_layer=0, num_hidden_layers=40)
    kinds = lfm2.layers_of(whole)
    assert [k["ffn"] for k in kinds[:3]] == ["dense", "dense", "sparse"]
    assert [i for i, k in enumerate(kinds) if k["mixer"] == "full"] == \
        [2, 6, 10, 14, 18, 22, 26, 30, 34, 38]
    assert lfm2.head_dim(CONF) == CONF["head_dim"] == 64


#: the catalog row's ``config`` (``model-configs`` guide), as published
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": (["conv", "conv", "full_attention", "conv"] * 10),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_every_published_width_is_kept_and_the_cut_is_stated():
    differs = sorted(k for k, v in PUBLISHED.items() if CONF[k] != v)
    assert differs == sorted(CONF["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (CONF["num_hidden_layers"], CONF["num_experts"],
            CONF["vocab_size"]) == (5, 16, 8192)
    assert CONF["published"] == {k: PUBLISHED[k] for k in differs}
    assert CONF["num_experts_published"] == 64
    assert CONF["held_experts"] == [0, 16]
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "4 chips" in CONF["deployment"]
    assert {"tied_head", "gate_order", "router", "expert_bias", "training",
            "init"} <= set(CONF["assumed"])
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b"]
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]
    assert entry["file"] == "benchmark/configs/lfm2_24b_a2b.json"


def test_flops_per_record_is_the_derivation():
    f = lfm2.flops_per_record(CONF)
    assert f["total"] == CONF["flops_per_record"] == 10901935620096
    conv = 4 * 2048 * 2048
    attn = 2048 * (2 * 2048 + 2 * 512)
    sparse = 2048 * 64 + 3 * 2048 * 1536 * (4 * 16 / 64)
    active = 4 * conv + attn + 3 * 2048 * 11776 + 4 * sparse + 2048 * 8192
    assert active == 204996608
    assert f["matrix_products"] == 6 * 204996608 * 8192
    assert f["attention"] == 3 * 4 * 64 * 32 * (8192 * 8193 // 2)
    assert f["convolution"] == 6 * 4 * 2048 * 3 * 8192
    for key in f:
        assert str(f[key]) in CONF["flops_derivation"] or key == "total"


def test_short_conv_byte_arithmetic():
    shape = CONF["shortconv_kernel_args"]
    assert shape == {"tokens": 2 * 8192, "channels": 2048, "taps": 3,
                     "itemsize": 2, "gate_out": "in_matmul", "layers": 4}
    n = 16384 * 2048 * 2
    taps = 2048 * 3 * 4
    whole = dict(shape, gate_out="whole")
    assert short_conv.least_bytes("fwd", **whole) == 4 * n + taps
    assert short_conv.least_bytes("bwd", **whole) == 7 * n + 2 * taps
    assert short_conv.least_bytes("fwd", **shape) == 3 * n + taps
    assert short_conv.least_bytes("bwd", **shape) == 5 * n + taps
    assert short_conv.least_seconds("fwd", 819e9, **shape) == \
        (3 * n + taps) / 819e9


FWD = ("%fusion.264 = (f32[]{:T(128)}, bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)})"
       " fusion(bf16[2,8192,6144]{2,1,0:T(8,128)(2,1)} %fusion.370), "
       "kind=kLoop, calls=%fused_computation.557")
AGAIN = ("%slice_multiply_fusion.5 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} "
         "fusion(bf16[2,8192,6144]{2,1,0:T(8,128)(2,1)} %fusion.456), "
         "kind=kLoop, calls=%fused_computation.811")
TAPS = ("%fusion.285 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} fusion("
        "bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} %slice_multiply_fusion.5, "
        "f32[2048]{0:T(1024)} %bitcast.590, f32[2048]{0:T(1024)} %bitcast.587,"
        " f32[2048]{0:T(1024)} %bitcast.584), kind=kLoop, "
        "calls=%fused_computation.578")
BWD = ("%slice_multiply_fusion.19 = (bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)}, "
       "bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)}) fusion(bf16[2,8192,6144]"
       "{2,1,0:T(8,128)(2,1)} %fusion.444, bf16[2,8192,2048]{2,1,0:T(8,128)"
       "(2,1)} %get-tuple-element.688, bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} "
       "%get-tuple-element.687), kind=kLoop, calls=%fused_computation.825")
FILTER = ("%fusion.789 = (f32[2048,1]{1,0}, f32[2048,1]{1,0}, f32[2048,1]{1,0})"
          " fusion(bf16[2048,3]{1,0} %copy-done.281), kind=kLoop, "
          "calls=%fused_computation.1725")
# what is NOT the convolution's own: the projections around it (their
# fusions compute the output gate too), a norm, the routed layer, attention
IN_PROJ = ("%fusion.370 = bf16[2,8192,6144]{2,1,0:T(8,128)(2,1)} fusion("
           "bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} %x, f32[2048]{0} %w, "
           "f32[2,8192]{1,0} %r, bf16[6144,2048]{1,0:T(8,128)(2,1)} %p), "
           "kind=kOutput, calls=%fused_computation.570")
OUT_PROJ = ("%fusion.208 = (f32[2,8192]{1,0}, bf16[2,8192,2048]{2,1,0}, f32[]) "
            "fusion(bf16[2,8192,2048]{2,1,0} %h, bf16[2048,2048]{1,0} %w, "
            "bf16[2,8192,2048]{2,1,0} %g, bf16[2,8192,6144]{2,1,0} %p, "
            "f32[2048]{0} %a, f32[2048]{0} %b, f32[2048]{0} %c), kind=kOutput,"
            " calls=%fused_computation.408")
NORM = ("%fusion.3 = bf16[2,8192,2048]{2,1,0} fusion(bf16[2,8192,2048]{2,1,0} "
        "%x, f32[2048]{0} %w, f32[2,8192]{1,0} %r), kind=kLoop, calls=%f")
ROUTED = "%scatter = f32[65536,2048]{1,0} fusion(f32[65536,2048]{1,0} %p)"
ATTN_FWD = ("%attn.4 = (bf16[64,8192,64]{2,1,0:T(8,128)(2,1)}, f32[64,8192,1]"
            "{2,1,0:T(8,128)}) custom-call(bf16[64,8192,64]{2,1,0} %q, "
            "bf16[16,8192,64]{2,1,0} %k, bf16[16,8192,64]{2,1,0} %v), "
            "custom_call_target=\"tpu_custom_call\"")


def test_the_cells_patterns_find_their_events_and_no_others():
    conv = CONF["shortconv_match"]
    for event in (FWD, AGAIN, TAPS, BWD, FILTER):
        assert re.search(conv, event), event
    for event in (IN_PROJ, OUT_PROJ, NORM, ROUTED, ATTN_FWD):
        assert not re.search(conv, event), event
    found = lambda event: [k["name"] for k in CONF["shortconv_kernels"]  # noqa: E731
                           if re.search(k["match"], event)]
    assert found(FWD) == found(AGAIN) == ["shortconv.fwd"]
    assert found(BWD) == ["shortconv.bwd"]
    assert not found(TAPS) and not found(FILTER) and not found(OUT_PROJ)
    assert re.search(CONF["routed_match"], ROUTED)
    assert not re.search(CONF["routed_match"], FWD)
    assert [k["name"] for k in CONF["attention_kernels"]
            if re.search(k["match"], ATTN_FWD)] == ["attn64.fwd"]
    cell = run.load_cell(CELL)
    assert {m["name"] for m in cell["per_layer"]} == {
        "kernel.shortconv_share", "kernel.shortconv_roofline",
        "kernel.attn64_share", "kernel.attn64_roofline",
        "moe.routed64_share", "step.mfu", "step.device_ms",
        "input.wait_share", "input.wait_p90_ms", "dispatch.ms_per_step"}
    assert cell["workload"]["batch"] == 2 and cell["chips"] == 1
    for name in ("kernel.shortconv_share", "kernel.shortconv_roofline",
                 "kernel.attn64_share", "kernel.attn64_roofline",
                 "moe.routed64_share"):
        spec = run.read_json(run.HERE, "layer_metrics", name + ".json")
        assert spec["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            run.HERE, "readers", spec["reader"] + ".py"))


def _ctx(ops, conf=CONF):
    return {"cell": {"config": conf}, "lo": 0.0, "hi": 10.0,
            "device_kind": "TPU v5 lite",
            "peaks": run.read_json(run.HERE, "peaks.json"),
            "trace": trace.Trace([trace.DeviceTrace("d", ops)])}


def test_shortconv_roofline_counts_calls_from_the_trace():
    """A layer's step: a forward, the forward again under ``nn.Remat`` and
    a backward; the filter fusions and the taps' fusion add time and no
    call; a projection adds neither; a call the window cuts gives its
    time and no call."""
    ops = [(FWD, 0.0, 0.5), (IN_PROJ, 0.5, 2.0), (FILTER, 2.0, 2.1),
           (AGAIN, 3.0, 3.5), (TAPS, 3.5, 4.0), (BWD, 5.0, 6.0),
           (OUT_PROJ, 6.0, 9.0)]
    shape = CONF["shortconv_kernel_args"]
    least = 2 * short_conv.least_seconds("fwd", 819e9, **shape) \
        + short_conv.least_seconds("bwd", 819e9, **shape)
    assert shortconv_roofline.read(_ctx(ops)) == pytest.approx(
        100.0 * least / 2.6)
    cut = ops + [(BWD, 9.5, 11.0)]
    assert shortconv_roofline.read(_ctx(cut)) == pytest.approx(
        100.0 * least / 3.1)
    spec = run.read_json(run.HERE, "layer_metrics",
                         "kernel.shortconv_share.json")
    assert matched_share.read(_ctx(ops), **spec["args"]) == \
        pytest.approx(100.0 * 2.6 / 7.1)
    assert shortconv_roofline.read(_ctx([(OUT_PROJ, 1.0, 2.0)])) is None
    # a configuration (or a program) without the convolution says nothing
    assert shortconv_roofline.read(_ctx(ops, conf={})) is None


def test_a_tiny_lfm2_plan_goes_through_the_harness_and_is_correct():
    import jax

    cell = tiny_cell("tiny_lfm2.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1


def test_the_control_fails_the_tiny_plans_limits():
    cell = tiny_cell("tiny_lfm2.c1")
    nums = control.control_numbers(cell, seed=2 ** 31 + 23)
    assert not compare.judge(nums, cell["workload"]["limits"]), nums
    assert nums["grad1_worst_leaf_gap"] > \
        10 * cell["workload"]["limits"]["grad1_worst_leaf_gap"]


@pytest.mark.parametrize("fault", ["bias-in-the-weights",
                                   "bias-forgotten-in-the-choice"])
def test_a_router_that_misplaces_its_bias_is_not_correct(fault, monkeypatch):
    """What only this family has in its router: the bias chooses and does
    not weigh.  A program that weighs by ``s + b``, or chooses by ``s``
    alone, runs, trains and is refused, because the seed draws ``b``."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn

    def route(self, x2):
        logits = jnp.dot(x2.astype(jnp.float32),
                         self.router.weight.T.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        biased = scores + self.select_bias.astype(jnp.float32)
        if fault == "bias-in-the-weights":
            top_p, top_i = jax.lax.top_k(biased, self.top_k)
        else:
            top_p, top_i = jax.lax.top_k(scores + 0.0 * biased, self.top_k)
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-6)
        return self.routed_scale * top_p, top_i

    monkeypatch.setattr(nn.RoutedExperts, "route", route)
    cell = tiny_cell("tiny_lfm2.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is False and out["failed"] == 0
    limits = cell["workload"]["limits"]
    assert out["compared"]["grad1_worst_leaf_gap"] > \
        3 * limits["grad1_worst_leaf_gap"]
