"""Transformer language models.

The reference has no attention or transformer models (SURVEY §5
"Long-context ... Absent"); these exist to exercise what the TPU build
adds on top of the reference's sequence story (RNN/TimeDistributed).

``build_transformer_lm`` returns a small causal decoder LM in the 2017
style: token embedding + learned positions -> N pre-norm
TransformerBlocks (LayerNorm, multi-head attention, GELU MLP) -> final
LayerNorm -> vocab head (log-probs per position, so
``TimeDistributedCriterion(ClassNLLCriterion(), size_average=True)``
trains it — size_average averages the per-step losses; the default sums
them, scaling the loss by sequence length).  It is the registry's
``transformer`` at toy widths, the model the generation server and the
sequence-parallel dry runs use; ``sp_mesh``/``sp_axis``/``sp_strategy``
route every block's attention through shard_map'd ring or Ulysses
attention for sequences larger than one chip holds.

``build_decoder_lm`` builds a current decoder from a per-layer plan
(:class:`DecoderPlan`): RMS normalisation, rotary positions, a mixer
whose kind differs by layer (grouped-query attention, full or within a
window, with a gate a head or a channel and optionally normed queries
and keys; latent attention, whose queries, keys and values come through
low-rank latents and whose rotary key is one head shared by all; gated
delta-rule linear attention; a double-gated short convolution; or a
Mamba-2 state-space mixer), and a dense gated or a
routed sparse feed-forward per layer (softmax scores, or sigmoid scores
with a bias that chooses; experts on the model's width or in a latent),
either of which a layer may lack, every block under ``nn.Remat``, the
head its own matrix or the embedding's.  Four scalar multipliers a model
may publish are the plan's too (on the embedding's output, on the
attention scores, on what each part adds to the residual stream, and a
divisor of the logits), each doing nothing at its default, and so is the
residual path: one stream and a ``+`` by default, or ``residual_streams``
streams that every block reads, writes and mixes through an
``nn.HyperConnection`` a part (manifold-constrained hyper-connections:
the mixing matrix made doubly stochastic by Sinkhorn's iterations), with
the embedding copied into the streams and their sum before the final
norm.  It trains with the same criterion; the
benchmark's ``laguna_s_2_1``, ``qwen3_next_80b_a3b``, ``lfm2_24b_a2b``,
``nemotron_3_super_120b_a12b``, ``granite_4_0_h_micro`` and
``xing4_0_29b_a4b`` configurations are such plans at published widths.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import Module, Parameter

__all__ = ["build_transformer_lm", "PositionalEmbedding", "build_decoder_lm",
           "DecoderPlan", "LayerPlan", "VocabHead", "tiny_decoder_plan"]


class PositionalEmbedding(Module):
    """Learned absolute positions added to token embeddings.

    Under a DECODE generation trace (``serving/generate``) the input is
    one token per row and its absolute position is that row's cache
    length, not 0 — the ambient cache context supplies the per-row
    positions the same way it supplies the per-layer caches."""

    def __init__(self, max_len: int, embed_dim: int):
        super().__init__()
        self.max_len = max_len
        self.weight = Parameter(jnp.zeros((max_len, embed_dim), jnp.float32))

    def update_output(self, input):
        from bigdl_tpu.nn.layers.attention import generation_cache_context

        ctx = generation_cache_context()
        if ctx is not None and ctx.mode == "decode":
            pos = ctx.positions()  # [B] absolute position per row
            return input + self._params["weight"][pos, :][:, None, :]
        s = input.shape[1]
        return input + self._params["weight"][None, :s, :]


def build_transformer_lm(vocab_size: int, num_layers: int = 4,
                         embed_dim: int = 256, num_heads: int = 8,
                         max_len: int = 1024, mlp_ratio: int = 4,
                         dropout: float = 0.0, backend="auto",
                         sp_mesh=None, sp_axis: str = "seq",
                         sp_strategy: str = "ring",
                         sp_batch_axis=None,
                         remat: bool = False,
                         scan: Optional[bool] = None) -> nn.Module:
    """Causal decoder-only LM over [batch, seq] token ids.
    ``sp_batch_axis`` composes sequence parallelism with data
    parallelism on a 2-D (data, seq) mesh; ``remat`` wraps each block in
    ``nn.Remat`` so long-context activations are recomputed, not stored.
    ``scan`` stacks the N identical blocks into one ``nn.ScanLayers``
    body so XLA compiles ONE block instead of N (None = the
    ``BIGDL_SCAN_LAYERS`` config; docs/compile.md)."""
    if sp_mesh is not None:
        from bigdl_tpu.parallel.sequence import (
            make_sequence_parallel_attention)

        backend = make_sequence_parallel_attention(
            sp_mesh, strategy=sp_strategy, axis_name=sp_axis, causal=True,
            batch_axis=sp_batch_axis)
    model = nn.Sequential(
        nn.LookupTable(vocab_size, embed_dim),
        PositionalEmbedding(max_len, embed_dim),
    )
    for _ in range(num_layers):
        block = nn.TransformerBlock(embed_dim, num_heads,
                                    mlp_ratio=mlp_ratio, dropout=dropout,
                                    causal=True, backend=backend)
        model.add(nn.Remat(block) if remat else block)
    model.add(nn.LayerNorm(embed_dim))
    model.add(nn.TimeDistributed(nn.Sequential(
        nn.Linear(embed_dim, vocab_size), nn.LogSoftMax())))
    from bigdl_tpu.nn.layers.scan import maybe_scan

    return maybe_scan(model, scan)


#: the kinds of mixer and of feed-forward a :class:`LayerPlan` may name
ATTENTION_KINDS = ("full", "window", "latent", "linear", "conv", "ssm",
                   "none")
FFN_KINDS = ("dense", "sparse", "none")


class LayerPlan(NamedTuple):
    """One decoder layer: ``attention`` is the kind of its MIXER,
    ``"full"``, ``"window"``, ``"latent"``, ``"linear"``, ``"conv"`` or
    ``"ssm"`` (the field keeps the name it had when every mixer was an
    attention; a ``"latent"`` layer is an :class:`nn.LatentAttention`, a
    ``"conv"`` layer an :class:`nn.GatedShortConv`, an ``"ssm"`` layer
    an :class:`nn.Mamba2Mixer`, and the last two attend to nothing),
    ``heads`` its query heads (of a ``"latent"`` layer: its heads, each
    with keys and values of its own out of the shared latent, so the
    plan's ``kv_heads`` is not read; of a linear layer: its value heads;
    of an ``"ssm"`` layer: its state-space heads; a ``"conv"`` layer has
    none and ignores it), ``ffn`` ``"dense"`` or ``"sparse"``.  ``"none"`` in
    either place: the layer lacks that part and is the other alone, one
    norm and one residual add (both ``"none"`` is refused)."""
    attention: str
    heads: int
    ffn: str


class DecoderPlan(NamedTuple):
    """Everything :func:`build_decoder_lm` builds from.  ``rotary_full``
    / ``rotary_window`` are the :class:`nn.Rotary` of each attention
    kind; ``held`` the ``(first, count)`` experts a sparse layer has
    here of its ``n_experts``; ``normalize`` whether the chosen experts'
    weights are renormalised to sum to one before ``routed_scale``.
    ``gate`` is the attention layers' (``None``, ``"per_head"``,
    ``"per_channel"``), ``qk_norm`` whether they norm each head's query
    and key, ``zero_centred_norm`` whether every norm of the model but a
    linear layer's gated one scales by ``1 + w``, ``shared_gate`` whether
    a sparse layer gates its shared expert.  A ``"linear"`` layer is an
    :class:`nn.GatedDeltaNet` of ``linear_key_heads`` key heads, head
    sizes ``linear_key_dim`` / ``linear_value_dim`` and a convolution of
    ``linear_conv`` taps; a ``"conv"`` layer an :class:`nn.GatedShortConv`
    of ``conv_taps`` taps.  ``router_score`` (``"softmax"`` or
    ``"sigmoid"``) and ``router_bias`` (a bias an expert that enters the
    choice of the ``top_k`` and not their weights) are the sparse
    layers' (:class:`nn.RoutedExperts`), as are ``expert_latent`` (the
    routed experts work on rows projected to this width and back; None:
    on the model's own) and ``expert_activation`` (``"silu"``: gated-SiLU
    experts and shared expert; another of ``nn.layers.moe.ACTIVATIONS``:
    ungated ones under it).  An ``"ssm"`` layer is an
    :class:`nn.Mamba2Mixer` of heads of ``ssm_head_dim``, ``ssm_groups``
    groups, a state of ``ssm_state`` and a convolution of ``ssm_conv``
    taps.  ``tie_embeddings``: the head projects with the embedding's own
    matrix, one parameter read in two places.  A ``"latent"`` layer is an
    :class:`nn.LatentAttention` whose queries come through a latent of
    ``q_rank`` and whose keys and values through one of ``kv_rank``; a
    head's query and key are ``head_dim`` (the part without positions)
    plus ``rope_dim`` (rotated by ``rotary_full``, which is then a rotary
    of ``rope_dim`` dimensions; the key's is one head shared by all)
    wide, its value ``value_dim``.

    ``residual_streams`` > 1: manifold-constrained hyper-connections.
    The embedding is copied into that many streams, every block's parts
    sit inside an :class:`nn.HyperConnection` each (``sinkhorn_iters``
    iterations, ``residual_clamp`` on the mixing logits, ``residual_eps``
    in the streams' norm) and the final norm reads the streams' sum.  At
    1 none of it is built.

    Four scalars, each of which multiplies nothing at its default:
    ``embedding_scale`` the embedding's output is multiplied by;
    ``attention_scale`` every attention layer's scores are multiplied by
    before their softmax (None: ``1 / sqrt(head_dim)``);
    ``residual_scale`` what a block's mixer and its feed-forward each add
    to the residual stream is multiplied by; ``logit_scale`` the head's
    logits are DIVIDED by before the log-softmax.  With
    ``tie_embeddings`` the one matrix's gradient is the lookup's, scaled
    by the first, plus the head's, scaled by one over the last."""
    vocab_size: int
    hidden_size: int
    head_dim: int
    kv_heads: int
    layers: Sequence[LayerPlan]
    window: int
    rotary_full: Optional[nn.Rotary]
    rotary_window: Optional[nn.Rotary]
    dense_width: int
    expert_width: int = 0
    shared_width: int = 0
    n_experts: int = 0
    top_k: int = 0
    held: Optional[Tuple[int, int]] = None
    routed_scale: float = 1.0
    gate: Optional[str] = "per_head"
    eps: float = 1e-6
    normalize: bool = True
    qk_norm: bool = False
    zero_centred_norm: bool = False
    shared_gate: bool = False
    linear_key_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    conv_taps: int = 3
    router_score: str = "softmax"
    router_bias: bool = False
    tie_embeddings: bool = False
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    expert_latent: Optional[int] = None
    expert_activation: str = "silu"
    embedding_scale: float = 1.0
    attention_scale: Optional[float] = None
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    q_rank: int = 0
    kv_rank: int = 0
    rope_dim: int = 0
    value_dim: int = 0
    residual_streams: int = 1
    sinkhorn_iters: int = 20
    residual_clamp: float = 30.0
    residual_eps: float = 1e-6


class VocabHead(Module):
    """Vocabulary projection (no bias) and log-softmax per position, the
    log-softmax in float32 whatever the activations' dtype.  ``tied_to``:
    the model's :class:`nn.LookupTable`, whose ``[vocab, embed]`` matrix
    is then the projection too (borrowed, not owned: the model has ONE
    such parameter, and its gradient is the embedding's plus the
    head's).  ``logit_scale``: the logits are divided by it before the
    log-softmax (a model's ``logits_scaling``); at 1 nothing is."""

    def __init__(self, embed_dim: int, vocab_size: int,
                 tied_to: Optional[nn.LookupTable] = None,
                 logit_scale: float = 1.0):
        super().__init__()
        self.logit_scale = logit_scale
        if tied_to is None:
            self.proj = nn.Linear(embed_dim, vocab_size, with_bias=False)
        else:
            if (tied_to.n_index, tied_to.n_output) != (vocab_size, embed_dim):
                raise ValueError(
                    f"a head of {vocab_size} x {embed_dim} tied to a table "
                    f"of {tied_to.n_index} x {tied_to.n_output}")
            self.borrow("embedding", tied_to)
        self.tied = tied_to is not None

    def update_output(self, input):
        if self.tied:
            logits = jnp.dot(
                input, self.embedding.weight.T.astype(input.dtype))
        else:
            logits = self.proj.forward(input)
        logits = logits.astype(jnp.float32)
        if self.logit_scale != 1.0:
            logits = logits / self.logit_scale
        return jax.nn.log_softmax(logits, axis=-1)


def build_decoder_lm(plan: DecoderPlan, remat: bool = True,
                     backend: str = "auto") -> nn.Module:
    """Causal decoder-only LM over [batch, seq] token ids, layer by
    layer as ``plan`` says; output log-probs [batch, seq, vocab]."""
    for i, layer in enumerate(plan.layers):
        # a plan is checked whole before a module of it is built
        if layer.attention not in ATTENTION_KINDS:
            raise ValueError(f"layer {i}: unknown attention kind "
                             f"{layer.attention!r}; known: "
                             f"{', '.join(ATTENTION_KINDS)}")
        if layer.ffn not in FFN_KINDS:
            raise ValueError(f"layer {i}: unknown feed-forward kind "
                             f"{layer.ffn!r}; known: {', '.join(FFN_KINDS)}")
        if layer.attention == layer.ffn == "none":
            raise ValueError(f"layer {i} has neither a mixer nor a "
                             f"feed-forward")
    zero = plan.zero_centred_norm

    def head_norm(n):
        return nn.RMSNorm(n, plan.eps, zero_centred=zero)

    # a tied table stays on the dense path: the head's gradient is dense
    # and has to meet the embedding's in one leaf
    embedding = nn.LookupTable(plan.vocab_size, plan.hidden_size,
                               sparse=False if plan.tie_embeddings else None)
    model = nn.Sequential(embedding)
    if plan.embedding_scale != 1.0:
        model.add(nn.MulConstant(plan.embedding_scale))
    streams = plan.residual_streams
    if streams > 1:
        model.add(nn.StreamExpand(streams))
    for layer in plan.layers:
        windowed = layer.attention == "window"
        if layer.attention == "none":
            attn = None
        elif layer.attention == "ssm":
            attn = nn.Mamba2Mixer(
                plan.hidden_size, layer.heads, plan.ssm_head_dim,
                plan.ssm_groups, plan.ssm_state, taps=plan.ssm_conv,
                eps=plan.eps)
        elif layer.attention == "conv":
            attn = nn.GatedShortConv(plan.hidden_size, taps=plan.conv_taps)
        elif layer.attention == "latent":
            attn = nn.LatentAttention(
                plan.hidden_size, layer.heads, plan.head_dim, plan.rope_dim,
                plan.value_dim, plan.q_rank, plan.kv_rank,
                rotary=plan.rotary_full, scale=plan.attention_scale,
                eps=plan.eps, backend=backend)
        elif layer.attention == "linear":
            attn = nn.GatedDeltaNet(
                plan.hidden_size, plan.linear_key_heads, layer.heads,
                plan.linear_key_dim, plan.linear_value_dim,
                conv_width=plan.linear_conv, eps=plan.eps)
        else:
            attn = nn.GroupedQueryAttention(
                plan.hidden_size, layer.heads, plan.kv_heads, plan.head_dim,
                window=plan.window if windowed else None,
                rotary=plan.rotary_window if windowed else plan.rotary_full,
                gate=plan.gate, backend=backend,
                qk_norm=head_norm if plan.qk_norm else None,
                scale=plan.attention_scale)
        if layer.ffn == "none":
            ffn = None
        elif layer.ffn == "dense":
            ffn = nn.GatedMLP(plan.hidden_size, plan.dense_width)
        else:
            ffn = nn.RoutedExperts(
                plan.hidden_size, plan.expert_width, plan.n_experts,
                plan.top_k, held=plan.held, shared_width=plan.shared_width,
                routed_scale=plan.routed_scale, normalize=plan.normalize,
                shared_gate=plan.shared_gate, score=plan.router_score,
                select_bias=plan.router_bias,
                activation=plan.expert_activation,
                latent=plan.expert_latent)
        block = nn.DecoderBlock(
            plan.hidden_size, attn, ffn, eps=plan.eps, zero_centred=zero,
            residual_scale=plan.residual_scale, streams=streams,
            sinkhorn_iters=plan.sinkhorn_iters,
            residual_clamp=plan.residual_clamp,
            residual_eps=plan.residual_eps)
        model.add(nn.Remat(block) if remat else block)
    if streams > 1:
        model.add(nn.StreamSum(streams))
    model.add(nn.RMSNorm(plan.hidden_size, plan.eps, zero_centred=zero))
    model.add(VocabHead(plan.hidden_size, plan.vocab_size,
                        tied_to=embedding if plan.tie_embeddings else None,
                        logit_scale=plan.logit_scale))
    return model


def tiny_decoder_plan(vocab_size: int = 256) -> DecoderPlan:
    """The registry's ``decoder_lm``: every mechanism of the builder at
    a width a CPU trains (dense then window, window, full layers; 16
    experts, 4 held, 3 a token)."""
    return DecoderPlan(
        vocab_size=vocab_size, hidden_size=64, head_dim=16, kv_heads=2,
        layers=[LayerPlan("full", 4, "dense"), LayerPlan("window", 6,
                "sparse"), LayerPlan("window", 6, "sparse"),
                LayerPlan("full", 4, "sparse")],
        window=8, rotary_full=nn.Rotary(8, theta=500000.0, factor=4.0,
                                        original_max_position=32,
                                        attention_factor=1.1),
        rotary_window=nn.Rotary(16), dense_width=128, expert_width=32,
        shared_width=32, n_experts=16, top_k=3, held=(0, 4),
        routed_scale=2.5)
