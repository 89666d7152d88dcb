"""Attention layers: MultiHeadAttention, LayerNorm, TransformerBlock.

The reference has no attention (SURVEY §5); these extend the module
catalog so long-context transformer models are first-class citizens of
the framework.  The compute core routes to ``bigdl_tpu.ops``: dense
XLA-fused attention, the Pallas flash kernel, or a sequence-parallel
strategy (ring / Ulysses over a mesh ``seq`` axis).
"""

from __future__ import annotations

import math
import sys
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.layers.linear import Linear
from bigdl_tpu.nn.module import Module, Parameter

__all__ = ["LayerNorm", "MultiHeadAttention", "TransformerBlock",
           "Rotary", "GroupedQueryAttention", "LatentAttention",
           "DecoderBlock"]


#: the ``jax.named_scope`` around a latent-attention layer's work between
#: its first product and its output projection (the compiled step's
#: ``op_name`` metadata carries it, a device trace's events do not)
LATENT_SCOPE = "mla"


def generation_cache_context():
    """The ambient KV-cache context bound by a generation trace
    (``serving/generate/kv_cache.py``), or None.  Resolved through
    ``sys.modules`` so the nn layer never imports the serving stack:
    a process that never generated cannot have bound a context, and a
    process that did has the module loaded already."""
    mod = sys.modules.get("bigdl_tpu.serving.generate.kv_cache")
    return mod.current() if mod is not None else None


class LayerNorm(Module):
    """Layer normalization over the last dimension (extension beyond the
    reference catalog; required by the transformer stack)."""

    def __init__(self, normalized_size: int, eps: float = 1e-5,
                 affine: bool = True):
        super().__init__()
        self.normalized_size = normalized_size
        self.eps = eps
        self.affine = affine
        if affine:
            self.weight = Parameter(jnp.ones((normalized_size,)))
            self.bias = Parameter(jnp.zeros((normalized_size,)))

    def update_output(self, input):
        mu = jnp.mean(input, axis=-1, keepdims=True)
        var = jnp.var(input, axis=-1, keepdims=True)
        out = (input - mu) * jax.lax.rsqrt(var + self.eps)
        if self.affine:
            out = out * self._params["weight"] + self._params["bias"]
        return out

    def __repr__(self):
        return f"LayerNorm({self.normalized_size})"


class MultiHeadAttention(Module):
    """Multi-head attention over [batch, seq, embed] inputs.

    ``backend``: 'auto' (on TPU: flash when ``max(Sq, Sk)`` reaches
    ``bigdl_tpu.ops.attention.flash_min_seq()`` — default 512, env
    ``BIGDL_FLASH_MIN_SEQ`` — else dense, which below one k-block is one
    batched MXU matmul; always dense off-TPU), 'dense',
    'flash', or a callable ``f(q, k, v) -> out`` over [B, H, S, D] arrays
    with causal/scale baked in — e.g. a shard_map-wrapped ring/ulysses
    attention from
    ``bigdl_tpu.parallel.sequence.make_sequence_parallel_attention``.
    Custom callables do not receive masks; pass masking via the callable's
    own construction.  ``dropout`` is applied to the attention context
    (before the output projection) in training mode.

    Input: a single tensor (self-attention), ``(x, mask)``,
    ``(query, key, value)``, or ``(query, key, value, mask)``, where
    tensors are [B, S, E] and mask broadcasts to [B, H, Sq, Sk]
    (True = attend).
    """

    def __init__(self, embed_dim: int, num_heads: int,
                 dropout: float = 0.0, with_bias: bool = True,
                 causal: bool = False, backend="auto"):
        super().__init__()
        assert embed_dim % num_heads == 0, \
            "embed_dim must be divisible by num_heads"
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.causal = causal
        self.dropout_p = dropout
        self.backend = backend
        self.q_proj = Linear(embed_dim, embed_dim, with_bias=with_bias)
        self.k_proj = Linear(embed_dim, embed_dim, with_bias=with_bias)
        self.v_proj = Linear(embed_dim, embed_dim, with_bias=with_bias)
        self.out_proj = Linear(embed_dim, embed_dim, with_bias=with_bias)
        if dropout > 0.0:
            from bigdl_tpu.nn.layers.normalization import Dropout

            self.drop = Dropout(dropout)

    def _split(self, x):
        b, s, _ = x.shape
        return x.reshape(b, s, self.num_heads, self.head_dim).transpose(
            0, 2, 1, 3)

    def _attend(self, q, k, v, mask):
        from bigdl_tpu.ops import dot_product_attention, flash_attention

        backend = self.backend
        if callable(backend):
            if mask is not None:
                raise ValueError(
                    "custom attention backends do not accept masks; bake "
                    "masking into the callable")
            return backend(q, k, v)
        if backend == "flash" and mask is not None:
            raise ValueError(
                "backend='flash' does not support masks (only causal=True); "
                "use backend='dense' or 'auto' for masked attention")
        if backend == "auto":
            from bigdl_tpu.ops.attention import select_attention_backend
            from bigdl_tpu.ops.dispatch import note

            # dense below the threshold, flash at/above it.  With the
            # round-5 block defaults (1024/512) flash BEATS dense from
            # seq 512 up (exp_attention_backend: 734 vs 562 seq/s — the
            # earlier "flash was 53% of the seq-512 step" profile was an
            # artifact of the old 128x128 blocks).  The routing rule
            # itself lives in ops.attention, its one home, and
            # honors the BIGDL_KERNELS kill switch.
            backend, reason = select_attention_backend(
                q.shape[2], k.shape[2], mask is not None)
            note("attention",
                 "pallas" if backend == "flash" else "xla", reason)
        if backend == "flash":
            return flash_attention(q, k, v, causal=self.causal)
        return dot_product_attention(q, k, v, mask=mask, causal=self.causal)

    def update_output(self, input):
        mask = None
        if isinstance(input, (tuple, list)):
            if len(input) == 2:
                x, mask = input
                xq = xk = xv = x
            elif len(input) == 3:
                xq, xk, xv = input
            elif len(input) == 4:
                xq, xk, xv, mask = input
            else:
                raise ValueError("input must be x, (x, mask), (q, k, v) or "
                                 "(q, k, v, mask)")
        else:
            xq = xk = xv = input
        if xq is xk and xk is xv:
            # self-attention: ONE [*, E] @ [E, 3E] GEMM instead of three
            # [*, E] @ [E, E] with the same left operand — better MXU
            # tiling. The weight concat is tiny next to the activation
            # matmul; gradients flow through it back to the separate
            # q/k/v parameters, so state_dict layout is unchanged.
            # Deliberate tradeoff: this bypasses Linear.forward, so
            # get_times() attributes the fused GEMM to THIS module, not
            # per-projection.
            w = jnp.concatenate([self.q_proj.weight, self.k_proj.weight,
                                 self.v_proj.weight], axis=0)
            qkv = jnp.dot(xq, w.T.astype(xq.dtype))
            if self.q_proj.with_bias:
                b_all = jnp.concatenate([self.q_proj.bias, self.k_proj.bias,
                                         self.v_proj.bias])
                qkv = qkv + b_all.astype(qkv.dtype)
            q, k, v = (self._split(t)
                       for t in jnp.split(qkv, 3, axis=-1))
        else:
            q = self._split(self.q_proj.forward(xq))
            k = self._split(self.k_proj.forward(xk))
            v = self._split(self.v_proj.forward(xv))
        ctx = generation_cache_context()
        out = None
        if ctx is not None and self.causal and xq is xk:
            # generation trace: prefill RECORDS the fresh k/v (and falls
            # through to the normal backend below — long prompts keep
            # the flash path); decode scatters the single new k/v row
            # into this layer's cache and returns q-against-cache
            # attention (dense by the q_len=1 routing rule)
            out = ctx.attend(q, k, v, causal=self.causal)
        if out is None:
            out = self._attend(q, k, v, mask)
        b, h, s, d = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        if self.dropout_p > 0.0:
            out = self.drop.forward(out)
        return self.out_proj.forward(out)

    def __repr__(self):
        return (f"MultiHeadAttention({self.embed_dim}, heads="
                f"{self.num_heads}, causal={self.causal})")


class TransformerBlock(Module):
    """Pre-norm transformer block: LN -> MHA -> residual, LN -> MLP ->
    residual.  The building block of ``build_transformer_lm``."""

    def __init__(self, embed_dim: int, num_heads: int, mlp_ratio: int = 4,
                 dropout: float = 0.0, causal: bool = True, backend="auto"):
        super().__init__()
        self.ln1 = LayerNorm(embed_dim)
        self.attn = MultiHeadAttention(embed_dim, num_heads, dropout=dropout,
                                       causal=causal, backend=backend)
        self.ln2 = LayerNorm(embed_dim)
        self.fc1 = Linear(embed_dim, embed_dim * mlp_ratio)
        self.fc2 = Linear(embed_dim * mlp_ratio, embed_dim)

    def update_output(self, input):
        x = input + self.attn.forward(self.ln1.forward(input))
        h = self.fc1.forward(self.ln2.forward(x))
        h = jax.nn.gelu(h)
        return x + self.fc2.forward(h)


class Rotary(NamedTuple):
    """Rotary position embedding (Su et al. 2021) of one attention layer.

    ``dims`` leading dimensions of every head are rotated in the
    rotate-half pairing (dimension i with i + dims/2), the rest pass
    through (a partial rotary factor).  ``factor`` > 1 selects YaRN (Peng
    et al. 2023): the inverse frequencies are blended, dimension by
    dimension, between the plain ones (extrapolation) and the plain ones
    divided by ``factor`` (interpolation) along the linear ramp between
    the dimensions that make ``beta_fast`` and ``beta_slow`` rotations
    over ``original_max_position`` positions, and cos and sin are
    multiplied by ``attention_factor``."""

    dims: int
    theta: float = 10000.0
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self) -> np.ndarray:
        half = self.dims // 2
        plain = self.theta ** (-np.arange(half, dtype=np.float64) / half)
        if self.factor == 1.0:
            return plain

        def ramp_dim(rotations):
            return self.dims * math.log(self.original_max_position / (
                rotations * 2 * math.pi)) / (2 * math.log(self.theta))

        low = max(math.floor(ramp_dim(self.beta_fast)), 0)
        high = min(math.ceil(ramp_dim(self.beta_slow)), self.dims - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
        return plain / self.factor * ramp + plain * (1.0 - ramp)

    def tables(self, positions: int):
        """``(cos, sin)``, each ``[positions, dims / 2]`` float32."""
        angle = np.arange(positions, dtype=np.float64)[:, None] \
            * self.inv_freq()[None, :]
        return ((np.cos(angle) * self.attention_factor).astype(np.float32),
                (np.sin(angle) * self.attention_factor).astype(np.float32))

    def apply(self, x):
        """Rotate ``x`` [B, S, heads, head_dim] at positions 0..S-1, in
        float32, and hand it back in its own dtype."""
        cos, sin = (jnp.asarray(t)[None, :, None, :]
                    for t in self.tables(x.shape[1]))
        half = self.dims // 2
        x32 = x.astype(jnp.float32)
        a, b, rest = (x32[..., :half], x32[..., half:self.dims],
                      x32[..., self.dims:])
        out = jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)
        return out.astype(x.dtype)


def _attend_causal(op, backend, q, k, v, **facts):
    """Causal attention of [B, H, S, D] arrays on the leg ``backend`` names
    (``auto``: ``ops.attention.select_attention_backend``'s rule),
    announced as ``kernel/dispatch op=<op>`` with the layer's ``facts``
    (its ``scale``, and its ``window`` if it has one, are the call's too)
    and, for the flash leg, its blocks."""
    from bigdl_tpu.ops.attention import (dot_product_attention,
                                         flash_attention, flash_blocks,
                                         select_attention_backend)
    from bigdl_tpu.ops.dispatch import note

    s, window = q.shape[2], facts.get("window")
    reason = "layer:backend"
    if backend == "auto":
        backend, reason = select_attention_backend(s, s)
    if backend == "flash":
        bq, bk, visited, total = flash_blocks(s, s, True, window)
        facts.update(block_q=bq, block_k=bk, blocks_visited=visited,
                     blocks_total=total)
    note(op, "pallas" if backend == "flash" else "xla", reason, **facts)
    attend = flash_attention if backend == "flash" else dot_product_attention
    return attend(q, k, v, causal=True, window=window, scale=facts["scale"])


class GroupedQueryAttention(Module):
    """Causal self-attention over [batch, seq, embed] with fewer key/
    value heads than query heads (Ainslie et al. 2023): query head h
    reads kv head ``h // (num_heads / num_kv_heads)``.  ``head_dim`` is
    free of ``embed_dim`` (the output projection maps ``num_heads *
    head_dim`` back), no projection has a bias.

    ``window``: None attends every earlier position; an integer keeps
    the last ``window`` of them (the position itself included).
    ``rotary``: a :class:`Rotary` applied to q and k, or None.
    ``gate="per_head"``: one sigmoid gate a head and position, computed
    from the layer's input, scales that head's output before the output
    projection.  ``gate="per_channel"``: one gate a channel of every
    head, and it comes out of the query projection, which is then twice
    as wide (a head's ``head_dim`` query channels, then its ``head_dim``
    gate channels).
    ``qk_norm``: a callable ``head_dim -> Module`` that makes the norm
    of one head's query and, called again, of one head's key (an
    ``RMSNorm``); both are applied before the rotation.
    ``scale``: what a score ``q . k`` is multiplied by before the softmax;
    None is ``1 / sqrt(head_dim)``, a number is the model's own (an
    ``attention_multiplier``), handed to whichever leg runs.

    ``backend``: ``auto`` (the rule of ``ops.attention.
    select_attention_backend``: the Pallas flash kernels on a TPU from
    ``flash_min_seq()`` positions up, which bound their key-block loop
    by the window and find kv heads by index; XLA's dense attention
    elsewhere), ``dense`` or ``flash``.  The decision is announced on a
    ``kernel/dispatch`` instant with ``window``, ``q_heads``,
    ``kv_heads``, ``scale`` (null: the default) and, for the flash leg,
    ``blocks_visited`` of ``blocks_total``.  No KV cache: this layer
    trains and scores; the generation path still runs
    ``MultiHeadAttention``."""

    def __init__(self, embed_dim: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, window: Optional[int] = None,
                 rotary: Optional[Rotary] = None,
                 gate: Optional[str] = None, backend: str = "auto",
                 qk_norm=None, scale: Optional[float] = None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over "
                             f"{num_kv_heads} kv heads")
        if gate not in (None, "per_head", "per_channel"):
            raise ValueError(f"unknown gate {gate!r}")
        self.embed_dim, self.head_dim = embed_dim, head_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.window, self.rotary, self.gate = window, rotary, gate
        self.backend, self.scale = backend, scale
        q_width = 2 * head_dim if gate == "per_channel" else head_dim
        self.q_proj = Linear(embed_dim, num_heads * q_width, with_bias=False)
        self.k_proj = Linear(embed_dim, num_kv_heads * head_dim,
                             with_bias=False)
        self.v_proj = Linear(embed_dim, num_kv_heads * head_dim,
                             with_bias=False)
        if gate == "per_head":
            self.gate_proj = Linear(embed_dim, num_heads, with_bias=False)
        if qk_norm is not None:
            self.q_norm, self.k_norm = qk_norm(head_dim), qk_norm(head_dim)
        self.qk_norm = qk_norm is not None
        self.out_proj = Linear(num_heads * head_dim, embed_dim,
                               with_bias=False)

    def _attend(self, q, k, v):
        return _attend_causal(
            "attention", self.backend, q, k, v, window=self.window, q_heads=self.num_heads,
            kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            gate=self.gate, qk_norm=self.qk_norm, scale=self.scale)

    def update_output(self, input):
        b, s, _ = input.shape
        h, g, d = self.num_heads, self.num_kv_heads, self.head_dim
        # one GEMM for every projection of the same input, as
        # MultiHeadAttention does; the parameters stay separate
        ws = [self.q_proj.weight, self.k_proj.weight, self.v_proj.weight]
        if self.gate == "per_head":
            ws.append(self.gate_proj.weight)
        fused = jnp.dot(input, jnp.concatenate(ws, axis=0).T.astype(
            input.dtype))
        q, k, v, *gate = jnp.split(
            fused, np.cumsum([w.shape[0] for w in ws])[:-1].tolist(), axis=-1)
        if self.gate == "per_channel":
            q, channel_gate = jnp.split(q.reshape(b, s, h, 2 * d), 2, axis=-1)
        q = q.reshape(b, s, h, d)
        k = k.reshape(b, s, g, d)
        if self.qk_norm:
            q, k = self.q_norm.forward(q), self.k_norm.forward(k)
        if self.rotary is not None:
            q, k = self.rotary.apply(q), self.rotary.apply(k)
        out = self._attend(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.reshape(b, s, g, d).transpose(0, 2, 1, 3))
        out = out.transpose(0, 2, 1, 3)          # [B, S, H, D]
        if gate:
            out = out * jax.nn.sigmoid(
                gate[0].astype(jnp.float32)).astype(out.dtype)[..., None]
        elif self.gate == "per_channel":
            out = out * jax.nn.sigmoid(
                channel_gate.astype(jnp.float32)).astype(out.dtype)
        return self.out_proj.forward(out.reshape(b, s, h * d))

    def __repr__(self):
        return (f"GroupedQueryAttention({self.embed_dim}, heads="
                f"{self.num_heads}/{self.num_kv_heads}, window={self.window})")


class LatentAttention(Module):
    """Causal multi-head latent attention over [batch, seq, embed]
    (DeepSeek-V2, arXiv:2405.04434): queries, keys and values all come
    through low-rank latents, and the rotary part of every head's key is
    ONE shared head.  ``u`` the layer's input, ``Norm`` an ``RMSNorm``
    (``eps``), ``H`` heads, no bias:

    - ``c_q = Norm_q(u W_qa)`` (``q_rank``); ``[q_nope, q_rope] =
      split_heads(c_q W_qb, H x [head_dim, rope_dim])``;
    - ``[c_kv, k_rope] = split(u W_kva, [kv_rank, rope_dim])``; ``c_kv <-
      Norm_kv(c_kv)``; ``[k_nope, v] = split_heads(c_kv W_kvb, H x
      [head_dim, value_dim])``;
    - ``q_h = [q_nope_h, rope(q_rope_h)]``, ``k_h = [k_nope_h,
      rope(k_rope)]``: the one rotated key under every head (``rotary``:
      a :class:`Rotary` of ``rope_dim`` dimensions, or None);
    - ``s_ij = scale q_i . k_j`` for ``j <= i`` (``scale`` None: ``1 /
      sqrt(head_dim + rope_dim)``; a model whose YaRN carries an
      ``mscale`` hands in its own), softmax in float32, ``o_h = sum_j
      p_ij v_j`` of ``value_dim``; ``concat_h(o_h) W_o``.

    Queries and keys are ``head_dim + rope_dim`` wide and values
    ``value_dim``: either leg (``backend`` as
    :class:`GroupedQueryAttention`'s) takes ``v`` of a width of its own.
    ``u W_qa`` and ``u W_kva`` are one product.  The decision is announced
    on a ``kernel/dispatch`` instant ``op=latent_attention`` with
    ``heads``, ``qk_dim``, ``rope_dim``, ``value_dim``, ``q_rank``,
    ``kv_rank``, ``scale`` and, for the flash leg, its blocks.  No latent
    cache: this layer trains and scores."""

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int,
                 rope_dim: int, value_dim: int, q_rank: int, kv_rank: int,
                 rotary: Optional[Rotary] = None,
                 scale: Optional[float] = None, eps: float = 1e-6,
                 backend: str = "auto"):
        super().__init__()
        from bigdl_tpu.nn.layers.normalization import RMSNorm

        if rotary is not None and rotary.dims != rope_dim:
            raise ValueError(f"a rotary of {rotary.dims} dimensions on a "
                             f"rotary key of {rope_dim}")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.head_dim, self.rope_dim, self.value_dim = \
            head_dim, rope_dim, value_dim
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.rotary, self.backend = rotary, backend
        self.scale = 1.0 / math.sqrt(head_dim + rope_dim) \
            if scale is None else scale
        self.q_a = Linear(embed_dim, q_rank, with_bias=False)
        self.q_norm = RMSNorm(q_rank, eps)
        self.q_b = Linear(q_rank, num_heads * (head_dim + rope_dim),
                          with_bias=False)
        self.kv_a = Linear(embed_dim, kv_rank + rope_dim, with_bias=False)
        self.kv_norm = RMSNorm(kv_rank, eps)
        self.kv_b = Linear(kv_rank, num_heads * (head_dim + value_dim),
                           with_bias=False)
        self.out_proj = Linear(num_heads * value_dim, embed_dim,
                               with_bias=False)

    def _attend(self, q, k, v):
        return _attend_causal(
            "latent_attention", self.backend, q, k, v, heads=self.num_heads, qk_dim=q.shape[-1], rope_dim=self.rope_dim,
            value_dim=self.value_dim, q_rank=self.q_rank,
            kv_rank=self.kv_rank, scale=self.scale)

    def update_output(self, input):
        b, s, _ = input.shape
        h, d, r, dv = (self.num_heads, self.head_dim, self.rope_dim,
                       self.value_dim)
        # one product for both latents of the same input
        fused = jnp.dot(input, jnp.concatenate(
            [self.q_a.weight, self.kv_a.weight], axis=0).T.astype(
                input.dtype))
        with jax.named_scope(LATENT_SCOPE):
            c_q, c_kv, k_rope = jnp.split(
                fused, [self.q_rank, self.q_rank + self.kv_rank], axis=-1)
            q = self.q_b.forward(self.q_norm.forward(c_q)).reshape(
                b, s, h, d + r)
            kv = self.kv_b.forward(self.kv_norm.forward(c_kv)).reshape(
                b, s, h, d + dv)
            q_rope, k_rope = q[..., d:], k_rope.reshape(b, s, 1, r)
            if self.rotary is not None:
                q_rope = self.rotary.apply(q_rope)
                k_rope = self.rotary.apply(k_rope)
            q = jnp.concatenate([q[..., :d], q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :d], jnp.broadcast_to(k_rope, (b, s, h, r))],
                axis=-1)
            out = self._attend(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               kv[..., d:].transpose(0, 2, 1, 3))
            out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
        return self.out_proj.forward(out)

    def __repr__(self):
        return (f"LatentAttention({self.embed_dim}, heads={self.num_heads}x"
                f"({self.head_dim}+{self.rope_dim})/{self.value_dim}, ranks="
                f"{self.q_rank}/{self.kv_rank})")


class DecoderBlock(Module):
    """Pre-norm decoder block with RMS normalisation: ``h = x +
    attn(norm1(x))``, ``y = h + ffn(norm2(h))``.  ``attn`` and ``ffn`` are
    modules over [batch, seq, embed] (a :class:`GroupedQueryAttention`, a
    :class:`LatentAttention`, a ``GatedDeltaNet``, a ``GatedShortConv`` or
    a ``Mamba2Mixer``; a ``GatedMLP`` or a ``RoutedExperts``);
    ``zero_centred`` is the norms'
    (``RMSNorm``).  Either part may be ``None``: the block is then the
    ONE sub-layer it has, ``y = x + attn(norm1(x))`` or ``y = x +
    ffn(norm2(x))``, one norm and one residual add (the layers of a
    decoder whose every layer is a mixer or a feed-forward alone).
    ``residual_scale`` multiplies what each part adds, ``h = x +
    residual_scale * attn(norm1(x))`` and the same for ``ffn`` (a model's
    ``residual_multiplier``); at 1 nothing is multiplied.

    ``streams`` > 1: the block carries that many residual streams
    ([batch, seq, streams * embed]) and each part it has sits inside an
    ``nn.HyperConnection`` (``hc_attn``, ``hc_ffn``; built with
    ``sinkhorn_iters``, ``residual_clamp``, ``residual_eps``), which reads
    the part's input from the streams and writes its output back to them
    in place of the ``+``; at 1 none is built and the block is the one
    above."""

    def __init__(self, embed_dim: int, attn: Optional[Module],
                 ffn: Optional[Module], eps: float = 1e-6,
                 zero_centred: bool = False, residual_scale: float = 1.0,
                 streams: int = 1, sinkhorn_iters: int = 20,
                 residual_clamp: float = 30.0, residual_eps: float = 1e-6):
        super().__init__()
        from bigdl_tpu.nn.layers.hyper_connection import HyperConnection
        from bigdl_tpu.nn.layers.normalization import RMSNorm

        if attn is None and ffn is None:
            raise ValueError("a decoder block with neither a mixer nor a "
                             "feed-forward")
        self.residual_scale, self.streams = residual_scale, streams

        def path():
            return HyperConnection(embed_dim, streams, sinkhorn_iters,
                                   residual_clamp, residual_eps)

        if attn is not None:
            if streams > 1:
                self.hc_attn = path()
            self.norm1 = RMSNorm(embed_dim, eps, zero_centred)
            self.attn = attn
        if ffn is not None:
            if streams > 1:
                self.hc_ffn = path()
            self.norm2 = RMSNorm(embed_dim, eps, zero_centred)
            self.ffn = ffn
        self.parts = tuple(name for name, part in (("attn", attn),
                                                   ("ffn", ffn))
                           if part is not None)

    def _scaled(self, part):
        return part if self.residual_scale == 1.0 \
            else part * self.residual_scale

    def _around(self, path, norm, part, x):
        """One part inside its hyper-connection, on the streams ``x``."""
        u, mix = path.forward(x)
        return path.merge(x, self._scaled(part.forward(norm.forward(u))), mix)

    def update_output(self, input):
        h = input
        if self.streams > 1:
            if "attn" in self.parts:
                h = self._around(self.hc_attn, self.norm1, self.attn, h)
            if "ffn" in self.parts:
                h = self._around(self.hc_ffn, self.norm2, self.ffn, h)
            return h
        if "attn" in self.parts:
            h = h + self._scaled(self.attn.forward(self.norm1.forward(h)))
        if "ffn" in self.parts:
            h = h + self._scaled(self.ffn.forward(self.norm2.forward(h)))
        return h
