"""The ``qwen3_next`` family: its parameter list lines up with the
program's, its parameters and FLOPs are the derivation's, the catalog's
widths are kept, the delta rule's roofline arithmetic and the reader's
call counting are what their docstrings say, and a tiny hybrid plan goes
through the harness on the CPU in float32 and is judged correct, which
the int8 control is not."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import compare, control, run, trace  # noqa: E402
from benchmark.kernels import delta_rule  # noqa: E402
from benchmark.models import qwen3_next  # noqa: E402
from benchmark.readers import delta_roofline  # noqa: E402
from rehearse import tiny_cell  # noqa: E402

CONF = run.read_json(run.HERE, "configs", "qwen3_next_80b_a3b.json")
CELL = "qwen3_next_80b_a3b.train.s16384.b1.c1"


def test_param_specs_line_up_with_the_programs_state_dict():
    from bigdl_tpu.nn.module import state_dict

    conf = tiny_cell("tiny_qwen3_next.c1")["config"]
    own = state_dict(qwen3_next.build(conf), kind="param")
    specs = qwen3_next.param_specs(conf)
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    # the published plan, by its specs alone (no 2.5 GB model is built)
    specs = qwen3_next.param_specs(CONF)
    sizes = {s["name"]: int(np.prod(s["shape"])) for s in specs}
    assert sum(sizes.values()) == CONF["parameters"] == 625667136
    mixer = lambda i: sum(  # noqa: E731
        v for k, v in sizes.items() if k.startswith(f"layer{i}.")
        and k.split(".")[1] in ("conv", "A_log", "dt_bias", "qkvz", "ba",
                                "head_norm", "o", "q", "k", "v", "q_norm",
                                "k_norm"))
    assert [mixer(i) for i in range(4)] == [33718464] * 3 + [27263488]
    experts = sum(v for k, v in sizes.items() if k.startswith("layer0.")
                  and k.split(".")[1] in ("experts", "router", "shared",
                                          "shared_gate"))
    assert experts == 104859648
    assert qwen3_next.layers_of(CONF) == ["linear"] * 3 + ["full"]


#: the catalog row's ``config`` (``model-configs`` guide), as published
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_every_published_width_is_kept_and_the_cut_is_stated():
    differs = sorted(k for k, v in PUBLISHED.items() if CONF[k] != v)
    assert differs == sorted(CONF["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (CONF["num_hidden_layers"], CONF["num_experts"],
            CONF["vocab_size"]) == (4, 32, 18992)
    assert CONF["published"] == {k: PUBLISHED[k] for k in differs}
    assert CONF["num_experts_published"] == 512
    assert CONF["held_experts"] == [0, 32]
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "16 chips" in CONF["deployment"]
    assert {"fused_projection_layout", "left_out", "init"} <= \
        set(CONF["assumed"])
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "qwen3_next_80b_a3b"]
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]
    assert entry["file"] == "benchmark/configs/qwen3_next_80b_a3b.json"


def test_flops_per_record_is_the_derivation():
    f = qwen3_next.flops_per_record(CONF)
    assert f["total"] == CONF["flops_per_record"] == 26241310654464
    linear = 2048 * (2048 + 2048 + 4096 + 4096 + 64) + 4096 * 2048
    full = 2048 * (16 * 2 * 256 + 2 * 2 * 256) + 16 * 256 * 2048
    sparse = 2048 * 512 + 2048 + 3 * 2048 * 512 * (1 + 10 * 32 / 512)
    active = 3 * linear + full + 4 * sparse + 2048 * 18992
    assert active == 191864832
    assert f["matrix_products"] == 6 * 191864832 * 16384
    assert f["attention"] == 3 * 4 * 256 * 16 * (16384 * 16385 // 2)
    chunk = 2 * 2 * 64 * 64 * 128 + 64 * 64 * 256 + 3 * 2 * 64 * 128 * 128 \
        + 2 * 64 * 64 * 128
    assert chunk == delta_rule.chunk_flops(64, 128, 128) == 10485760
    assert f["delta_rule"] == 3 * 3 * 32 * 256 * chunk
    assert f["convolution"] == 6 * 3 * 8192 * 4 * 16384
    for key in f:
        assert str(f[key]) in CONF["flops_derivation"] or key == "total"


def test_delta_rule_roofline_arithmetic():
    shape = CONF["delta_kernel_args"]
    assert shape == {**qwen3_next.delta_shape(CONF), "itemsize": 2,
                     "layers": 3}
    assert delta_rule.flops("bwd", **shape) == 2 * delta_rule.flops(
        "fwd", **shape) == 2 * 32 * 256 * 10485760
    # a length the chunk does not divide is one more chunk
    assert delta_rule.flops("fwd", 1, 65, 8, 8, 64) == \
        2 * delta_rule.chunk_flops(64, 8, 8)
    qk = vo = 32 * 16384 * 128 * 2
    row = 32 * 16384 * 4
    assert delta_rule.least_bytes("fwd", **shape) == 2 * qk + 2 * vo + 2 * row
    assert delta_rule.least_bytes("bwd", **shape) == 4 * qk + 3 * vo + 4 * row
    # at the v5e's peaks the bytes bound both directions, not the FLOPs
    for direction in ("fwd", "bwd"):
        assert delta_rule.least_seconds(direction, 197e12, 819e9, **shape) \
            == delta_rule.least_bytes(direction, **shape) / 819e9 \
            > delta_rule.flops(direction, **shape) / 197e12


def _ctx(ops):
    return {"cell": {"config": CONF}, "lo": 0.0, "hi": 10.0,
            "device_kind": "TPU v5 lite",
            "peaks": run.read_json(run.HERE, "peaks.json"),
            "trace": trace.Trace([trace.DeviceTrace("d", ops)])}


SOLVE = ("%custom-call.3 = f32[1,8,256,1,64,64]{2,4,5,3,1,0:T(8,128)} "
         "custom-call(f32[1,8,256,1,64,64]{2,5,4,3,1,0:T(8,128)} %fusion.1)"
         ", custom_call_target=\"InvertDiagBlocksLowerTriangular\"")
_FWD = ("(s32[]{:T(128)}, f32[1,32,128,128]{3,2,1,0}, "
        "bf16[256,1,32,64,128]{4,3,2,1,0}, bf16[256,1,32,64,64]{3,4,0,2,1})")
_BWD = _FWD[:-1] + ", /*index=4*/bf16[256,1,32,64,64]{3,4,0,2,1})"
SCAN = f"%while.6 = {_FWD} while({_FWD} %tuple.82), condition=%c, body=%b"
BACK = f"%while.7 = {_BWD} while({_BWD} %tuple.83), condition=%c, body=%b"
BODY = "%fusion.9 = f32[32,64,128]{2,1,0} fusion(bf16[32,64,128]{2,1,0} %p)"
EDGE = ("%copy.9 = bf16[1,32,16384,128]{3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[1,32,16384,128]{2,3,1,0:T(8,128)(2,1)} %bitcast.146)")
_WIDE = "bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}"
_ROW = "f32[32,16384]{1,0:T(8,128)}"
_CALL = (f"custom-call({_WIDE} %q, {_WIDE} %k, {_WIDE} %v, {_ROW} %g, "
         f"{_ROW} %beta), custom_call_target=\"tpu_custom_call\"")
CALL_FWD = (f"%rule.1 = ({_WIDE}, f32[32,128,128]{{2,1,0:T(8,128)}}) "
            + _CALL)
CALL_BWD = f"%rule.2 = ({_WIDE}, {_WIDE}, {_WIDE}, {_ROW}, {_ROW}) " + _CALL
OTHER = "%fusion.7 = bf16[16384,2048]{1,0} fusion(bf16[16384,2048]{1,0} %p)"


def test_delta_roofline_counts_forwards_and_backwards_from_the_trace():
    """Two forwards (one computed again) and one backward, as a block
    under ``nn.Remat`` runs them: three scans, of which one names the
    in-chunk matrix twice.  The body's events lie inside their scan and
    count once; another layer's event does not count; a scan the window
    cuts gives its time and no call."""
    ops = [(SOLVE, 0.0, 0.5), (SCAN, 1.0, 2.0), (BODY, 1.2, 1.4),
           (SOLVE, 3.0, 3.5), (SCAN, 4.0, 5.0), (BACK, 6.0, 8.0),
           (OTHER, 8.0, 9.0)]
    shape = CONF["delta_kernel_args"]
    least = 2 * delta_rule.least_seconds("fwd", 197e12, 819e9, **shape) \
        + delta_rule.least_seconds("bwd", 197e12, 819e9, **shape)
    assert delta_roofline.read(_ctx(ops)) == pytest.approx(
        100.0 * least / 5.0)
    cut = ops + [(BACK, 9.5, 11.0)]
    assert delta_roofline.read(_ctx(cut)) == pytest.approx(
        100.0 * least / 5.5)
    assert delta_roofline.read(_ctx([(OTHER, 1.0, 2.0)])) is None
    # a leg that is one custom call a direction is counted by its result
    # and timed with the transposes at the rule's edges
    kernel = [(EDGE, 0.0, 0.5), (CALL_FWD, 1.0, 2.0), (CALL_FWD, 4.0, 5.0),
              (CALL_BWD, 6.0, 8.0), (OTHER, 8.0, 9.0)]
    assert delta_roofline.read(_ctx(kernel)) == pytest.approx(
        100.0 * least / 4.5)
    bare = _ctx(ops)
    bare["cell"] = {"config": {}}
    assert delta_roofline.read(bare) is None


def test_the_cells_patterns_find_their_events_and_no_others():
    import re

    assert re.search(CONF["delta_match"], SOLVE)
    assert re.search(CONF["delta_match"], SCAN)
    assert re.search(CONF["delta_match"], BACK)
    found = lambda event: [k["name"] for k in CONF["delta_kernels"]  # noqa: E731
                           if re.search(k["match"], event)]
    assert found(SCAN) == ["delta.scan"]
    assert found(BACK) == ["delta.scan", "delta.bwd_scan"]
    assert found(CALL_FWD) == ["delta.call_fwd"]
    assert found(CALL_BWD) == ["delta.call_bwd"]
    assert found(CALL_FWD.replace("(bf16", "bf16", 1).replace(
        ", f32[32,128,128]{2,1,0:T(8,128)})", "")) == ["delta.call_fwd"]
    assert not found(EDGE) and not found(SOLVE) and not found(OTHER)
    for event in (EDGE, CALL_FWD, CALL_BWD):
        assert re.search(CONF["delta_match"], event)
    # another head block than 8 is found too
    assert re.search(CONF["delta_match"],
                     SOLVE.replace("[1,8,256,1,64,64]", "[1,16,256,1,64,64]"))
    assert re.search(CONF["delta_match"], BODY)
    assert not re.search(CONF["delta_match"], OTHER)
    routed = "%scatter = f32[40960,2048]{1,0} fusion(f32[40960,2048]{1,0} %p)"
    assert re.search(CONF["routed_match"], routed)
    assert not re.search(CONF["delta_match"], routed)
    assert not re.search(CONF["routed_match"], SCAN)
    fwd = ("%jvp__.1 = (bf16[16,16384,256]{2,1,0:T(8,128)(2,1)}, "
           "f32[16,16384,1]{2,1,0:T(8,128)}) custom-call(bf16[16,16384,256]"
           "{2,1,0} %bitcast), custom_call_target=\"tpu_custom_call\"")
    found = [k["name"] for k in CONF["attention_kernels"]
             if re.search(k["match"], fwd)]
    assert found == ["attn_gated.fwd"]
    assert not re.search(CONF["delta_match"], fwd)
    cell = run.load_cell(CELL)
    assert {m["name"] for m in cell["per_layer"]} >= {
        "kernel.delta_share", "kernel.delta_roofline",
        "kernel.attn_gated_roofline", "kernel.attn_gated_share",
        "moe.routed512_share", "step.mfu", "step.device_ms",
        "input.wait_share"}


def test_attention_share_reads_the_gated_kernels():
    from benchmark.readers import matched_share

    spec = run.read_json(run.HERE, "layer_metrics",
                         "kernel.attn_gated_share.json")
    assert spec["reader"] == "matched_share" and spec["workloads"] == [CELL]
    dq = ("%t.2 = bf16[16,16384,256]{2,1,0:T(8,128)(2,1)} custom-call("
          "bf16[16,16384,256]{2,1,0} %q), custom_call_target=\"tpu_custom_call\"")
    ops = [(dq, 0.0, 1.0), (OTHER, 1.0, 4.0), (SCAN, 4.0, 5.0)]
    assert matched_share.read(_ctx(ops), **spec["args"]) == \
        pytest.approx(20.0)
    assert matched_share.read(_ctx([(OTHER, 0.0, 1.0)]),
                              **spec["args"]) is None


def test_a_tiny_hybrid_plan_goes_through_the_harness_and_is_correct():
    import jax

    cell = tiny_cell("tiny_qwen3_next.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1


def test_the_control_fails_the_tiny_plans_limits():
    cell = tiny_cell("tiny_qwen3_next.c1")
    nums = control.control_numbers(cell, seed=2 ** 31 + 23)
    assert not compare.judge(nums, cell["workload"]["limits"]), nums
    assert nums["grad1_worst_leaf_gap"] > \
        10 * cell["workload"]["limits"]["grad1_worst_leaf_gap"]


def test_a_rule_that_drops_the_state_between_chunks_is_not_correct(
        monkeypatch):
    """What only this family has is the state carried from chunk to
    chunk.  A program whose every chunk starts from a zero state runs,
    trains and is refused: the fixture's 160 positions are two chunks and
    a half, and ``dt_bias`` is drawn so that some heads keep what they
    hold across them."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import delta_rule as program_rule

    whole = program_rule._chunked

    def forgetful(q, k, v, g, beta, chunk):
        parts = [whole(*(a[:, :, i:i + chunk] for a in (q, k, v, g, beta)),
                       chunk) for i in range(0, q.shape[2], chunk)]
        return jnp.concatenate([o for o, _ in parts], axis=2), parts[-1][1]

    monkeypatch.setattr(program_rule, "_chunked", forgetful)
    cell = tiny_cell("tiny_qwen3_next.c1")
    out = run.run_cell(cell, 2 ** 31 + 22, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is False and out["failed"] == 0
    limits = cell["workload"]["limits"]
    assert out["compared"]["grad1_worst_leaf_gap"] > \
        3 * limits["grad1_worst_leaf_gap"]
    assert out["compared"]["delta_worst_leaf_gap"] > \
        3 * limits["delta_worst_leaf_gap"]
