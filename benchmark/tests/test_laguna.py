"""The ``laguna`` family: its parameter list lines up with the program's,
its FLOPs are the derivation's, the attention kernels' element counts
are a brute-force mask's, and a tiny plan goes through the harness on
the CPU in float32 and is judged correct."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark import control, compare, run  # noqa: E402
from benchmark.kernels import attention  # noqa: E402
from benchmark.models import laguna  # noqa: E402
from rehearse import tiny_cell  # noqa: E402

CONF = run.read_json(run.HERE, "configs", "laguna_s_2_1.json")


def test_param_specs_line_up_with_the_programs_state_dict():
    from bigdl_tpu.nn.module import state_dict

    conf = tiny_cell("tiny_laguna.c1")["config"]
    own = state_dict(laguna.build(conf), kind="param")
    specs = laguna.param_specs(conf)
    assert [tuple(v.shape) for v in own.values()] == \
        [tuple(s["shape"]) for s in specs]
    # the published plan, by its specs alone (no 3 GB model is built)
    sizes = [int(np.prod(s["shape"])) for s in laguna.param_specs(CONF)]
    assert sum(sizes) == CONF["parameters"] == 811017216
    assert [l["heads"] for l in laguna.layers_of(CONF)] == [48, 72, 72, 72, 48]
    assert [l["attention"] for l in laguna.layers_of(CONF)] == \
        ["full", "window", "window", "window", "full"]
    assert [l["ffn"] for l in laguna.layers_of(CONF)] == \
        ["dense"] + ["sparse"] * 4


def test_every_published_width_is_kept_and_the_cut_is_stated():
    row = {"hidden_size": 3072, "head_dim": 128, "num_key_value_heads": 8,
           "num_attention_heads": 48, "intermediate_size": 12288,
           "moe_intermediate_size": 1024,
           "shared_expert_intermediate_size": 1024,
           "num_experts_per_tok": 10, "sliding_window": 512,
           "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06}
    assert {k: CONF[k] for k in row} == row
    assert CONF["num_experts_published"] == 256
    assert CONF["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (CONF["num_hidden_layers"], CONF["num_experts"],
            CONF["vocab_size"]) == (5, 8, 12544)
    assert CONF["published"] == {"num_hidden_layers": 48,
                                 "num_experts": 256, "vocab_size": 100352}
    assert len(CONF["layer_types"]) == 48          # the lists stay whole
    assert "32 chips" in CONF["deployment"]
    full = CONF["rope_parameters"]["full_attention"]
    assert (full["rope_theta"], full["factor"], full["partial_rotary_factor"],
            full["attention_factor"]) == (500000, 128, 0.5,
                                          1.4852030263919618)


def test_flops_per_record_is_the_derivation():
    f = laguna.flops_per_record(CONF)
    assert f["total"] == CONF["flops_per_record"] == 30000364388352
    # by hand: active matrix-product parameters a token, x 6 x 8192
    attn = lambda h: 3072 * (2 * h * 128 + 2 * 8 * 128 + h)  # noqa: E731
    sparse = 3072 * 256 + 3 * 3072 * 1024 * (1 + 10 * 8 / 256)
    params = 2 * attn(48) + 3 * attn(72) + 3 * 3072 * 12288 + 4 * sparse \
        + 3072 * 12544
    assert f["matrix_products"] == round(6 * params * 8192)
    full, window = 8192 * 8193 // 2, 512 * 513 // 2 + 7680 * 512
    assert f["attention"] == 3 * 4 * 128 * (2 * 48 * full + 3 * 72 * window)


@pytest.mark.parametrize("seq,window", [(7, None), (16, 4), (16, 16),
                                        (33, 5), (12, 40)])
def test_kept_elements_is_a_brute_force_mask_count(seq, window):
    i, j = np.meshgrid(np.arange(seq), np.arange(seq), indexing="ij")
    keep = j <= i
    if window is not None:
        keep &= i - j < window
    assert attention.kept_elements(seq, window) == int(keep.sum())


def test_attention_roofline_arithmetic():
    shape = CONF["attention_kernel_args"]["window"]
    kept = attention.kept_elements(8192, 512)
    assert attention.flops("fwd", **shape) == 2 * 2 * 128 * 72 * kept
    assert attention.flops("dkv", **shape) == 2 * attention.flops(
        "fwd", **shape)
    q, kv, row = 72 * 8192 * 128 * 2, 8 * 8192 * 128 * 2, 72 * 8192 * 4
    assert attention.least_bytes("dq", **shape) == 3 * q + 2 * kv + 2 * row
    # both forwards are bound by their FLOPs, the window's less so
    for family in ("window", "full"):
        shape = CONF["attention_kernel_args"][family]
        assert attention.least_seconds("fwd", 197e12, 819e9, **shape) == \
            attention.flops("fwd", **shape) / 197e12 > \
            attention.least_bytes("fwd", **shape) / 819e9


def test_a_tiny_plan_goes_through_the_harness_and_is_correct():
    import jax

    cell = tiny_cell("tiny_laguna.c1")
    out = run.run_cell(cell, 2 ** 31 + 21, 1.0, False, jax.devices()[:1],
                       log=lambda line: None)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1


def test_the_control_fails_the_tiny_plans_limits():
    cell = tiny_cell("tiny_laguna.c1")
    nums = control.control_numbers(cell, seed=2 ** 31 + 22)
    assert not compare.judge(nums, cell["workload"]["limits"]), nums
    assert nums["grad1_worst_leaf_gap"] > \
        10 * cell["workload"]["limits"]["grad1_worst_leaf_gap"]
