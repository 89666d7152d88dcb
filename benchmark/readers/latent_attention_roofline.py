"""Roofline share of the configuration's latent-attention kernels
(``latent_attention_kernels``: forward, dq, dkv): for every call that ran
whole inside the traced window, the least time the chip could take
(``benchmark/kernels/latent_attention.py``: the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, each product over its own width,
score elements counted exactly under the mask), summed, over the device
time those calls took.  Calls are counted from the trace, so recomputed
forwards count as the calls they are.  A configuration without the keys,
or a program whose trace holds no such call (the parent of the PR that
brought the layer), reports nothing."""

from benchmark.kernels import latent_attention
from benchmark.readers import attention_roofline, mfu


def read(ctx):
    conf = ctx["cell"]["config"]
    kernels = conf.get("latent_attention_kernels")
    shape = conf.get("latent_attention_kernel_args")
    if not kernels or not shape:
        return None
    least = took = 0.0
    for k in kernels:
        n, secs = attention_roofline.calls(ctx, k["match"])
        least += n * latent_attention.least_seconds(
            k["direction"], mfu.peak(ctx, "bf16_flops_per_s"),
            mfu.peak(ctx, "hbm_bytes_per_s"), **shape)
        took += secs
    return 100.0 * least / took if took > 0 and least > 0 else None
