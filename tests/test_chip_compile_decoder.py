"""The windowed, grouped flash-attention kernels at the shapes of the
benchmark's ``laguna_s_2_1`` cell, compiled for a described TPU v5e in
the style of ``test_chip_compile.py`` (whose fixtures describe the
topology inside a module-scoped fixture and steer ``is_tpu_device``):
``[1, 72, 8192, 128]`` queries over 8 kv heads under a 512 window, and
``[1, 48, 8192, 128]`` full.  Nothing executes.  The compiled text, with
operand shapes as a device trace names its events, is also what the
configuration's ``attention_kernels`` patterns have to find.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops import attention
from test_chip_compile import as_tpu, one_chip, topo  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = {"window": (72, 512), "full": (48, None)}


def _traced_text(heads, window, sharding):
    q = jax.ShapeDtypeStruct((1, heads, 8192, 128), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16,
                              sharding=sharding)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: attention.flash_attention(
            *a, causal=True, window=window), q, k, v)
        return out, vjp(do)

    compiled = jax.jit(fwd_bwd).lower(q, kv, kv, q).compile()
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    (module,) = compiled.runtime_executable().hlo_modules()
    return module.to_string(options)


@pytest.mark.parametrize("family", CASES)
def test_cell_attention_kernels_compile_and_are_found(family, one_chip,  # noqa: F811
                                                      as_tpu):  # noqa: F811
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
