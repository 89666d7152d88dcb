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
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

__all__ = [
    "FLASH_OUT",
    "FLASH_LSE",
    "dot_product_attention",
    "flash_attention",
    "flash_blocks",
    "flash_min_seq",
    "is_tpu_device",
    "select_attention_backend",
    "flash_auto",
    "attention_partial",
    "combine_partials",
]

_NEG_INF = -1e30

#: the names the flash forward kernel's output and logsumexp carry among
#: the backward rule's residuals (``jax.ad_checkpoint.checkpoint_name``):
#: ``nn.Remat`` keeps what they name, so its backward pass recomputes a
#: block without running the kernel again
FLASH_OUT = "flash_attention/out"
FLASH_LSE = "flash_attention/lse"


# ---------------------------------------------------------------------------
# dense reference
# ---------------------------------------------------------------------------

def dot_product_attention(q, k, v, mask=None, causal: bool = False,
                          scale: Optional[float] = None,
                          window: Optional[int] = None):
    """Dense softmax(q k^T / sqrt(d)) v.  mask: broadcastable to
    [B, H, Sq, Sk], True = attend.  ``window`` (with ``causal``) keeps,
    for query position i, the keys j with ``i - j < window``.  ``k``/``v``
    may carry fewer heads than ``q`` (grouped-query attention): query
    head h reads kv head ``h // (H / G)``, found by a reshape of ``q``,
    never by repeating ``k``.  ``v`` may be of another width than ``q``
    and ``k``: the result is as wide as ``v``."""
    if window is not None and not causal:
        raise ValueError("window attention is causal: pass causal=True")
    b, h, sq, d = q.shape
    g, sk = k.shape[1], k.shape[2]
    if h % g:
        raise ValueError(f"{h} query heads over {g} kv heads")
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    grouped = g != h
    if grouped:  # [B, G, H/G, Sq, D] against [B, G, Sk, D]
        q = q.reshape(b, g, h // g, sq, d)
        if mask is not None:
            mask = jnp.broadcast_to(mask, (b, h, sq, sk)).reshape(
                b, g, h // g, sq, sk)
    qk, pv = (("bgrqd,bgkd->bgrqk", "bgrqk,bgkd->bgrqd") if grouped
              else ("bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"))
    s = jnp.einsum(qk, q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
        k_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = q_pos >= k_pos
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
        s = jnp.where(keep, s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows output 0 (matching the flash/ring convention)
    # instead of softmax's uniform distribution over masked positions
    valid = jnp.max(s, axis=-1, keepdims=True) > _NEG_INF / 2
    p = jnp.where(valid, p, 0.0)
    out = jnp.einsum(pv, p.astype(v.dtype), v)
    return out.reshape(b, h, sq, v.shape[-1])


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
    backend in {"flash", "dense"} — the one home of the predicate:
    ``MultiHeadAttention`` and whatever accounts for its FLOPs read it
    here and never re-derive it (a round-5 copy of it had omitted the
    mask condition).

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


# Grid layout: (batch*q_heads, q_blocks, k_blocks VISITED) for fwd/dq and
# (batch*kv_heads, k_blocks, group, q_blocks VISITED) for dkv.  The
# innermost grid dimensions iterate sequentially on-core, so only one
# (block, d) tile of each operand is VMEM-resident at a time (k/v stream
# from HBM block-by-block) while the running online-softmax state lives
# in VMEM scratch — this is what keeps the kernel O(block) in VMEM at
# arbitrary sequence length.  m/l scratch is broadcast over 128 lanes to
# satisfy TPU tiling.
#
# Which blocks are visited: causality bounds a query block's key blocks
# from above, a window bounds them from below, so the inner grid
# dimension is the LARGEST count any query block needs (``_visits``) and
# the block index map adds the first needed block (``_k_bounds``); a
# step past a query block's last needed block re-reads that last block
# (no new DMA) and computes nothing.  Grouped-query attention: k/v keep
# their G heads in HBM and the index map sends query head h to kv head
# ``h // (H / G)``; the dk/dv kernel walks a kv head's H / G query heads
# in its third grid dimension and sums them in scratch.

_LANES = 128


def _causal_offset(q_len, kv_len):
    """off such that q row i attends k positions <= i + off."""
    return kv_len - q_len


def _mx(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _mn(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


class _Geom(NamedTuple):
    """The static facts every kernel and index map shares."""
    q_len: int
    kv_len: int
    bq: int
    bk: int
    causal: bool
    window: Optional[int]
    heads: int      # query heads
    kv_heads: int

    @property
    def off(self):
        return _causal_offset(self.q_len, self.kv_len)

    @property
    def nq(self):
        return self.q_len // self.bq

    @property
    def nk(self):
        return self.kv_len // self.bk

    @property
    def group(self):
        return self.heads // self.kv_heads

    def k_bounds(self, qi):
        """First and last key block the rows of query block ``qi`` need
        (``qi`` an int or a traced program id)."""
        q_start = qi * self.bq
        lo, hi = 0, self.nk - 1
        if self.causal:
            hi = _mn(hi, _mx(q_start + self.off + self.bq - 1, 0) // self.bk)
        if self.window is not None:
            lo = _mx(q_start + self.off - (self.window - 1), 0) // self.bk
            lo = _mn(lo, hi)
        return lo, hi

    def q_bounds(self, ki):
        """First and last query block that needs key block ``ki``."""
        k_start = ki * self.bk
        lo, hi = 0, self.nq - 1
        if self.causal:
            lo = _mn(_mx(k_start - self.off, 0) // self.bq, hi)
        if self.window is not None:
            hi = _mn(hi, _mx(k_start + self.bk - 2 + self.window - self.off,
                             0) // self.bq)
            hi = _mx(hi, lo)
        return lo, hi

    def k_visits(self):
        """(inner grid extent, blocks visited, blocks in all) of fwd/dq."""
        spans = [self.k_bounds(qi) for qi in range(self.nq)]
        counts = [hi - lo + 1 for lo, hi in spans]
        return max(counts), sum(counts), self.nq * self.nk

    def q_visits(self):
        spans = [self.q_bounds(ki) for ki in range(self.nk)]
        return max(hi - lo + 1 for lo, hi in spans)

    def keep(self, q_start, k_start):
        """[bq, bk] element mask of one block pair, None when no rule
        masks anything."""
        if not self.causal:
            return None
        q_pos = q_start + self.off + lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 0)
        k_pos = k_start + lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 1)
        keep = q_pos >= k_pos
        if self.window is not None:
            keep = keep & (q_pos - k_pos < self.window)
        return keep

    def interior(self, q_start, k_start):
        """True where every pair of the block attends, so the element
        mask can be skipped (most blocks of a long causal sequence)."""
        if not self.causal:
            return True
        inside = k_start + self.bk - 1 <= q_start + self.off
        if self.window is not None:
            inside = inside & (q_start + self.off + self.bq - 1 - k_start
                               < self.window)
        return inside

    def kv_head(self, bh):
        """Row of the flattened [B*G, S, D] k/v that flattened query
        head ``bh`` of [B*H, S, D] reads."""
        if self.group == 1:
            return bh
        return (bh // self.heads) * self.kv_heads \
            + (bh % self.heads) // self.group

    def q_head(self, bg, r):
        """Row of [B*H, S, D] of the ``r``-th query head of kv head ``bg``."""
        if self.group == 1:
            return bg
        return (bg // self.kv_heads) * self.heads \
            + (bg % self.kv_heads) * self.group + r


def _masked_and_plain(live, interior, step):
    """Run ``step(masked)`` under ``live``: with the element mask on the
    blocks an edge crosses, without it inside."""
    from jax.experimental import pallas as pl

    if interior is True:
        pl.when(live)(lambda: step(False))
        return
    pl.when(live & interior)(lambda: step(False))
    pl.when(live & jnp.logical_not(interior))(lambda: step(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_s, m_s, l_s, *, scale: float, geom: _Geom):
    from jax.experimental import pallas as pl

    qi, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    lo, hi = geom.k_bounds(qi)
    ki = lo + j
    q_start, k_start = qi * geom.bq, ki * geom.bk

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def _step(masked):
        q, k_blk, v_blk = q_ref[0], k_ref[0], v_ref[0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(geom.keep(q_start, k_start), s, _NEG_INF)
        m_prev = m_s[:, 0]
        l_prev = l_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        if masked:
            p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        m_s[...] = jnp.broadcast_to(m_new[:, None], m_s.shape)
        l_s[...] = jnp.broadcast_to(
            (l_prev * alpha + jnp.sum(p, axis=-1))[:, None], l_s.shape)
        acc_s[...] = acc_s[...] * alpha[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)

    _masked_and_plain(ki <= hi, geom.interior(q_start, k_start), _step)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_s[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_s[:, 0] + jnp.log(l_safe)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_s, *, scale: float, geom: _Geom):
    from jax.experimental import pallas as pl

    qi, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    lo, hi = geom.k_bounds(qi)
    ki = lo + j
    q_start, k_start = qi * geom.bq, ki * geom.bk

    @pl.when(j == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _step(masked):
        q, do = q_ref[0], do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        k_blk, v_blk = k_ref[0], v_ref[0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, None])
        if masked:
            p = jnp.where(geom.keep(q_start, k_start), p, 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_s[...] = dq_s[...] + jnp.dot(
            ds.astype(k_blk.dtype), k_blk,
            preferred_element_type=jnp.float32)

    _masked_and_plain(ki <= hi, geom.interior(q_start, k_start), _step)

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_s, dv_s, *, scale: float, geom: _Geom):
    from jax.experimental import pallas as pl

    ki, r, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nr, nj = pl.num_programs(2), pl.num_programs(3)
    lo, hi = geom.q_bounds(ki)
    qi = lo + j
    q_start, k_start = qi * geom.bq, ki * geom.bk

    @pl.when((r == 0) & (j == 0))
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _step(masked):
        k, v = k_ref[0], v_ref[0]
        q_blk, do_blk = q_ref[0], do_ref[0]
        lse_blk = lse_ref[0, :, 0]
        delta_blk = delta_ref[0, :, 0]
        s = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_blk[:, None])
        if masked:
            p = jnp.where(geom.keep(q_start, k_start), p, 0.0)
        dv_s[...] = dv_s[...] + jnp.dot(
            p.T.astype(do_blk.dtype), do_blk,
            preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None]) * scale
        dk_s[...] = dk_s[...] + jnp.dot(
            ds.T.astype(q_blk.dtype), q_blk,
            preferred_element_type=jnp.float32)

    _masked_and_plain(qi <= hi, geom.interior(q_start, k_start), _step)

    @pl.when((r == nr - 1) & (j == nj - 1))
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


def _geometry(q, k, causal, window, block_q, block_k) -> _Geom:
    sq, sk = q.shape[2], k.shape[2]
    return _Geom(sq, sk, _pick_block(sq, block_q), _pick_block(sk, block_k),
                 causal, window, q.shape[1], k.shape[1])


def _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret,
                    window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    g, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    geom = _geometry(q, k, causal, window, block_q, block_k)
    bq, bk = geom.bq, geom.bk
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * g, sk, d)
    vr = v.reshape(b * g, sk, dv)

    def q_map(bh, qi, j):
        return bh, qi, 0

    def k_map(bh, qi, j):
        lo, hi = geom.k_bounds(qi)
        return geom.kv_head(bh), _mn(lo + j, hi), 0

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, geom=geom),
        grid=(b * h, geom.nq, geom.k_visits()[0]),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), k_map),
            pl.BlockSpec((1, bk, dv), k_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, dv), lse.reshape(b, h, sq)


def _flash_bwd_impl(q, k, v, out, lse, do, scale, causal,
                    block_q, block_k, interpret, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    g, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    geom = _geometry(q, k, causal, window, block_q, block_k)
    bq, bk = geom.bq, geom.bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * g, sk, d)
    vr = v.reshape(b * g, sk, dv)
    dor = do.reshape(b * h, sq, dv)
    lser = lse.reshape(b * h, sq, 1)
    deltar = delta.reshape(b * h, sq, 1)

    def q_map(bh, qi, j):
        return bh, qi, 0

    def k_map(bh, qi, j):
        lo, hi = geom.k_bounds(qi)
        return geom.kv_head(bh), _mn(lo + j, hi), 0

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, geom=geom),
        grid=(b * h, geom.nq, geom.k_visits()[0]),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), k_map),
            pl.BlockSpec((1, bk, dv), k_map),
            pl.BlockSpec((1, bq, dv), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
            pl.BlockSpec((1, bq, 1), q_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)

    def kv_map(bg, ki, r, j):
        return bg, ki, 0

    def qrow_map(bg, ki, r, j):
        lo, hi = geom.q_bounds(ki)
        return geom.q_head(bg, r), _mn(lo + j, hi), 0

    dk, dvals = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, geom=geom),
        grid=(b * g, geom.nk, geom.group, geom.q_visits()),
        in_specs=[
            pl.BlockSpec((1, bq, d), qrow_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
            pl.BlockSpec((1, bq, dv), qrow_map),
            pl.BlockSpec((1, bq, 1), qrow_map),
            pl.BlockSpec((1, bq, 1), qrow_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * g, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * g, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, g, sk, d),
            dvals.reshape(b, g, sk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, window):
    out, _ = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                             interpret, window)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret,
                    window):
    out, lse = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                               interpret, window)
    # the two residuals that cost a kernel to rebuild, named for a
    # checkpoint policy to keep (the identity under any other)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, window,
                    res, do):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, do, scale, causal,
                           block_q, block_k, interpret, window)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_blocks(sq: int, sk: int, causal: bool = False,
                 window: Optional[int] = None) -> Tuple[int, int, int, int]:
    """``(block_q, block_k, blocks_visited, blocks_total)`` of one head
    of :func:`flash_attention` at its default blocks: what the attention
    layers announce on their ``kernel/dispatch`` instant."""
    block_q, block_k = _default_blocks(None, None, window)
    geom = _Geom(sq, sk, _pick_block(sq, block_q), _pick_block(sk, block_k),
                 causal, window, 1, 1)
    _, visited, total = geom.k_visits()
    return geom.bq, geom.bk, visited, total


def _default_blocks(block_q, block_k, window):
    """1024/512 (the round-5 sweep), or the environment's; under a
    window neither block is larger than the window (at least 128), so
    that a query block visits its own key block and the one before it
    and nothing else: window 512 -> 512/512, two key blocks a query
    block.  Smaller key blocks visit the same elements in twice the
    grid steps and cost twice the time: 512/256 read 10.4 ms for the
    forward of 72 heads at 8,192 positions and 7.4 for dk/dv where 512/512
    reads 5.5 and 4.1 (my chip runs, PR 28)."""
    if block_q is None:
        block_q = int(os.environ.get("BIGDL_FLASH_BLOCK_Q", "1024"))
        if window is not None:
            block_q = min(block_q, max(_LANES, window))
    if block_k is None:
        block_k = int(os.environ.get("BIGDL_FLASH_BLOCK_K", "512"))
        if window is not None:
            block_k = min(block_k, max(_LANES, window))
    return block_q, block_k


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Flash attention (Pallas TPU kernel).  ``q`` [B, H, S, D], ``k``
    [B, G, S, D] and ``v`` [B, G, S, Dv] with G dividing H (grouped-query
    attention: the block index map finds a query head's kv head, nothing
    is repeated); out [B, H, S, Dv].  ``v`` has a width of its own: the
    output, its cotangent, ``dv`` and the forward's accumulator follow
    ``v``; ``dq`` and ``dk`` follow ``q`` (latent attention runs heads of
    192 over values of 128 as they are).  ``window`` (with ``causal``)
    keeps keys j with ``i - j < window`` and bounds the key-block loop
    from below as causality bounds it from above.

    O(S) memory: softmax is computed online per q block over streamed k/v
    blocks; backward recomputes p from the saved logsumexp (no S x S
    materialization).  Off-TPU the kernels run in Pallas interpret mode so
    the identical code path is testable on the CPU mesh.  The matrix
    products take their operands in the inputs' dtype (bfloat16 inputs
    feed the MXU as bfloat16) and accumulate in float32; softmax
    statistics are float32.

    Block sizes default to 1024/512 (clamped to the sequence):
    the round-5 hardware sweep (BASELINE.md, "exp_flash_blocks")
    measured seq-4096 training 3.5x FASTER at 1024/512 than
    at the old 128/128 default — small blocks underfill the MXU and pay
    the grid-iteration overhead per tiny tile, exactly the short-seq
    pathology the auto backend routes to dense.  ``BIGDL_FLASH_BLOCK_Q``
    / ``BIGDL_FLASH_BLOCK_K`` override process-wide so sweeps need no
    code change.
    """
    if window is not None and not causal:
        raise ValueError("window attention is causal: pass causal=True")
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} "
                         f"kv heads")
    block_q, block_k = _default_blocks(block_q, block_k, window)
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if interpret is None:
        interpret = _use_interpret()
    sq, sk = q.shape[2], k.shape[2]
    bq, bk = _pick_block(sq, block_q), _pick_block(sk, block_k)
    if not interpret and ((bq % 8 and bq != sq) or (bk % 8 and bk != sk)):
        # shapes the Mosaic tiling can't express — dense fallback
        return dot_product_attention(q, k, v, causal=causal, scale=scale,
                                     window=window)
    return _flash(q, k, v, scale, causal, block_q, block_k, interpret,
                  window)
