"""Scaled dot-product attention: dense reference + Pallas flash kernel.

The reference framework has no attention of any kind (SURVEY §5
"Long-context ... Absent"); this is new TPU-first design work.  Three
entry points:

- ``dot_product_attention``: dense O(S^2)-memory reference (XLA-fused).
- ``flash_attention``: Pallas TPU kernel, O(S) memory, online softmax,
  with a full flash *backward* (dq / dkv kernels) via ``jax.custom_vjp``.
  Runs in interpret mode automatically off-TPU so tests exercise the same
  code path on the CPU mesh.
- ``attention_partial`` / ``combine_partials``: blockwise partial
  attention state (acc, m, l) and its merge — the algebra ring attention
  (``bigdl_tpu.parallel.sequence``) accumulates around the ICI ring.

Shapes follow [batch, heads, seq, head_dim] throughout.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "dot_product_attention",
    "flash_attention",
    "flash_min_seq",
    "is_tpu_device",
    "select_attention_backend",
    "flash_auto",
    "attention_partial",
    "combine_partials",
]

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# dense reference
# ---------------------------------------------------------------------------

def dot_product_attention(q, k, v, mask=None, causal: bool = False,
                          scale: Optional[float] = None):
    """Dense softmax(q k^T / sqrt(d)) v.  mask: broadcastable to
    [B, H, Sq, Sk], True = attend."""
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        q_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
        k_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows output 0 (matching the flash/ring convention)
    # instead of softmax's uniform distribution over masked positions
    valid = jnp.max(s, axis=-1, keepdims=True) > _NEG_INF / 2
    p = jnp.where(valid, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# blockwise partial state (used by ring attention)
# ---------------------------------------------------------------------------

def attention_partial(q, k, v, scale: float, mask=None):
    """One blockwise attention partial: returns (acc, m, l) where
    out = acc / l after all partials are combined.  mask broadcastable to
    [B, H, Sq, Sk], True = attend."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would pollute l
    p = jnp.where((s > _NEG_INF / 2)[..., :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return acc, m, l


def combine_partials(state_a, state_b):
    """Merge two attention partials with the online-softmax rescale."""
    acc_a, m_a, l_a = state_a
    acc_b, m_b, l_b = state_b
    m = jnp.maximum(m_a, m_b)
    alpha = jnp.exp(m_a - m)
    beta = jnp.exp(m_b - m)
    return (acc_a * alpha[..., None] + acc_b * beta[..., None],
            m, l_a * alpha + l_b * beta)


# ---------------------------------------------------------------------------
# Pallas flash attention
# ---------------------------------------------------------------------------

def is_tpu_device() -> bool:
    """True when the default jax device is TPU hardware.  A backend
    that fails to come up raises here: answering False would turn a
    broken accelerator into interpret mode or the XLA leg in silence."""
    dev = jax.devices()[0]
    return dev.platform == "tpu" or "tpu" in dev.device_kind.lower()


def _use_interpret() -> bool:
    """Mosaic-compile on TPU; Pallas interpret mode elsewhere (tests)."""
    return not is_tpu_device()


def flash_min_seq() -> int:
    """Sequence length at which ``backend='auto'`` switches from dense
    to flash attention (``BIGDL_FLASH_MIN_SEQ``, default 512).

    History of this threshold (both decisions measured on TPU v5e):
    the round-5 profile first showed flash at the OLD 128x128 default
    blocks consuming 53% of the seq-512 transformer_lm step (tiny
    per-head tiles underfill the 128x128 MXU; grid iteration dominates),
    so the gate was introduced at 1024.  The round-5 block sweep
    (`exp_flash_blocks`, BASELINE.md) then fixed the block defaults to
    1024/512 — 3.5x faster at seq 4096 — and the re-run A/B
    (`exp_attention_backend`) showed properly-blocked flash BEATING
    dense at seq 512 (734 vs 562 seq/s: the S^2 score tensor never
    round-trips HBM), so the default dropped to 512.  Below 512 the
    sequence is shorter than one k block and dense's single fused
    matmul still wins."""
    raw = os.environ.get("BIGDL_FLASH_MIN_SEQ", "512")
    try:
        return int(raw)
    except ValueError as e:
        # loud: a silently-defaulted threshold would make an A/B sweep
        # compare the wrong legs
        raise ValueError(
            f"BIGDL_FLASH_MIN_SEQ={raw!r} is not an integer") from e


def select_attention_backend(sq: int, sk: int,
                             masked: bool = False) -> Tuple[str, str]:
    """THE auto-backend routing decision — (backend, reason) with
    backend in {"flash", "dense"} — shared by ``MultiHeadAttention``
    and ``bench.py``'s flash-MFU correction so the two can never drift
    (round-5 advisor finding: the bench re-derived this predicate and
    omitted the mask condition).

    Rules, in order: the ``BIGDL_KERNELS`` kill switch (``xla`` ->
    dense everywhere, ``pallas`` -> flash wherever structurally legal),
    then the measured auto policy — flash on TPU hardware from
    ``flash_min_seq()`` up (judged on BOTH lengths so a short-query
    cross-attention over a long k/v still streams), dense below it,
    off-TPU, or in a step that XLA partitions over a mesh
    (``dispatch.spmd_partitioned``).  Dense masks (beyond ``causal``) always route dense: the
    flash kernel does not take a mask operand.  ``sq == 1`` — the KV-
    cached DECODE shape — always routes dense regardless of kv length
    (and regardless of ``BIGDL_KERNELS=pallas``): a flash q block is
    128 MXU rows of which decode fills exactly one, so the kernel would
    compute 127/128 padding per k block, while dense q_len=1 is a
    single batched matvec — exactly the shape the MXU handles without
    tiling ceremony."""
    from bigdl_tpu.ops.dispatch import auto_pallas, kernel_mode

    mode = kernel_mode()
    if mode == "xla":
        return "dense", "forced:BIGDL_KERNELS=xla"
    if sq == 1:
        return "dense", "decode:q_len=1"
    if masked:
        return "dense", "masked"
    if mode == "pallas":
        return "flash", "forced:BIGDL_KERNELS=pallas"
    ok, reason = auto_pallas()
    if not ok:  # off-TPU, or a step XLA partitions over a mesh
        return "dense", reason
    if max(sq, sk) < flash_min_seq():
        return "dense", "auto:below-min-seq"
    return "flash", "auto:tpu"


def flash_auto(sq: int, sk: int, masked: bool = False) -> bool:
    """True when the auto backend routes (sq, sk) to the flash kernel."""
    return select_attention_backend(sq, sk, masked)[0] == "flash"


# Grid layout: (batch*heads, q_blocks, k_blocks) for fwd/dq and
# (batch*heads, k_blocks, q_blocks) for dkv.  The innermost grid dimension
# iterates sequentially on-core, so only one (block, d) tile of each
# operand is VMEM-resident at a time (k/v stream from HBM block-by-block)
# while the running online-softmax state lives in VMEM scratch — this is
# what keeps the kernel O(block) in VMEM at arbitrary sequence length.
# m/l scratch is broadcast over 128 lanes to satisfy TPU tiling.

_LANES = 128


def _causal_offset(q_len, kv_len):
    """off such that q row i attends k positions <= i + off."""
    return kv_len - q_len


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_s, m_s, l_s, *,
                scale: float, causal: bool, q_len: int, kv_len: int):
    from jax.experimental import pallas as pl

    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k
    off = _causal_offset(q_len, kv_len)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    live = True
    if causal:
        live = q_start + off + block_q - 1 >= k_start

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_s[:, 0]
        l_prev = l_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - m_new[:, None]), 0.0)
        m_s[...] = jnp.broadcast_to(m_new[:, None], m_s.shape)
        l_s[...] = jnp.broadcast_to(
            (l_prev * alpha + jnp.sum(p, axis=-1))[:, None], l_s.shape)
        acc_s[...] = acc_s[...] * alpha[:, None] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_s[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_s[:, 0] + jnp.log(l_safe)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_s, *, scale: float, causal: bool, q_len: int, kv_len: int):
    from jax.experimental import pallas as pl

    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k
    off = _causal_offset(q_len, kv_len)

    @pl.when(ki == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    live = True
    if causal:
        live = q_start + off + block_q - 1 >= k_start

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - lse[:, None]), 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_s[...] = dq_s[...] + jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_s, dv_s, *,
                scale: float, causal: bool, q_len: int, kv_len: int):
    from jax.experimental import pallas as pl

    block_k, d = k_ref.shape[1], k_ref.shape[2]
    block_q = q_ref.shape[1]
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k
    off = _causal_offset(q_len, kv_len)

    @pl.when(qi == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    live = True
    if causal:
        live = q_start + off + block_q - 1 >= k_start

    @pl.when(live)
    def _step():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q_blk = q_ref[0].astype(jnp.float32)
        do_blk = do_ref[0].astype(jnp.float32)
        lse_blk = lse_ref[0, :, 0]
        delta_blk = delta_ref[0, :, 0]
        s = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + off + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - lse_blk[:, None]), 0.0)
        dv_s[...] = dv_s[...] + jnp.dot(
            p.T, do_blk, preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None]) * scale
        dk_s[...] = dk_s[...] + jnp.dot(
            ds.T, q_blk, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _pick_block(s: int, pref: int) -> int:
    if s <= pref:
        return s
    b = pref
    while s % b != 0:
        b //= 2
    return max(b, 1)


def _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    grid = (b * h, sq // bq, sk // bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               q_len=sq, kv_len=sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _flash_bwd_impl(q, k, v, out, lse, do, scale, causal,
                    block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    dor = do.reshape(b * h, sq, d)
    lser = lse.reshape(b * h, sq, 1)
    deltar = delta.reshape(b * h, sq, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          q_len=sq, kv_len=sk),
        grid=(b * h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          q_len=sq, kv_len=sk),
        grid=(b * h, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                             interpret)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                               interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, do, scale, causal,
                           block_q, block_k, interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention (Pallas TPU kernel).  [B, H, S, D] in/out.

    O(S) memory: softmax is computed online per q block over streamed k/v
    blocks; backward recomputes p from the saved logsumexp (no S x S
    materialization).  Off-TPU the kernels run in Pallas interpret mode so
    the identical code path is testable on the CPU mesh.

    Block sizes default to 1024/512 (clamped to the sequence):
    the round-5 hardware sweep (`tools/experiments/exp_flash_blocks.py`,
    BASELINE.md) measured seq-4096 training 3.5x FASTER at 1024/512 than
    at the old 128/128 default — small blocks underfill the MXU and pay
    the grid-iteration overhead per tiny tile, exactly the short-seq
    pathology the auto backend routes to dense.  ``BIGDL_FLASH_BLOCK_Q``
    / ``BIGDL_FLASH_BLOCK_K`` override process-wide so sweeps need no
    code change.
    """
    import os

    if block_q is None:
        block_q = int(os.environ.get("BIGDL_FLASH_BLOCK_Q", "1024"))
    if block_k is None:
        block_k = int(os.environ.get("BIGDL_FLASH_BLOCK_K", "512"))
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if interpret is None:
        interpret = _use_interpret()
    sq, sk = q.shape[2], k.shape[2]
    bq, bk = _pick_block(sq, block_q), _pick_block(sk, block_k)
    if not interpret and ((bq % 8 and bq != sq) or (bk % 8 and bk != sk)):
        # shapes the Mosaic tiling can't express — dense fallback
        return dot_product_attention(q, k, v, causal=causal, scale=scale)
    return _flash(q, k, v, scale, causal, block_q, block_k, interpret)

