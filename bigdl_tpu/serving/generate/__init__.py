"""LLM decode subsystem: KV cache + cached decode + continuous batching.

The one-shot serving stack (docs/serving.md) answers a request with a
single forward; autoregressive generation instead runs ONE forward per
emitted token over an ever-growing context.  Re-reading the whole
context every step is the transformer_lm_long MFU cliff (0.40 -> 0.19,
BASELINE.md round 5) — so generation gets its own data path, split the
way *Parallax* (arXiv 1808.02621) splits sparse from dense work:

- **prefill** — the prompt's one big forward.  Rides the existing shape
  buckets and the flash-attention auto backend, and WRITES the per-layer
  k/v projections into a cache (``kv_cache.CacheContext``);
- **decode** — one token per step, q_len=1 against the cache.  Dense
  attention is the right shape there (a 128-row flash q block would be
  127/128 padding — ``select_attention_backend`` hard-routes q_len=1 to
  dense), and steps COALESCE across every active request
  (``GenerationBatcher``) so the device sees one ``[B, 1]`` dispatch per
  iteration instead of B tiny ones.

Cache lengths live on a fixed closed set of buckets
(``kv_cache.cache_buckets`` — the PR-8 bucket discipline extended to the
time axis), so every decode executable is AOT-warmed at startup and the
retrace detector stays clean over any traffic mix.
"""

from bigdl_tpu.serving.generate.batcher import (GenerationBatcher,
                                                GenerationRequest,
                                                sample_token)
from bigdl_tpu.serving.generate.decode import GenerateExecutor
from bigdl_tpu.serving.generate.kv_cache import (CacheContext, StackedKVCache,
                                                 cache_buckets, current)

__all__ = [
    "CacheContext",
    "StackedKVCache",
    "cache_buckets",
    "current",
    "GenerateExecutor",
    "GenerationBatcher",
    "GenerationRequest",
    "default_seq_buckets",
    "generation_model",
    "sample_token",
]


def generation_model(name: str, num_classes: int = 0):
    """Build registry model ``name`` for generation serving — the ONE
    place the front-ends (``cli serve --generate``, ``bench_serving.py
    --generate``) share the rule: trace-order cache plumbing cannot
    address a ScanLayers stack (one traced body for N layers), so
    models whose registry build may scan are built unrolled here."""
    from bigdl_tpu.models import registry

    if name == "transformer":
        from bigdl_tpu.models import build_transformer_lm

        return build_transformer_lm(vocab_size=num_classes or 256,
                                    scan=False)
    if name not in registry.MODELS:
        raise ValueError(f"unknown model {name!r}; choose from "
                         f"{registry.model_names()}")
    return registry.build_model(name, num_classes)


def default_seq_buckets(spec):
    """Default prompt buckets when the operator gives none: halving
    steps down from the model's canonical length, so short prompts do
    not pay full-context prefill (the closed-set discipline holds —
    every bucket is AOT-warmed)."""
    s = int(spec.shape[1])
    return sorted({max(16, s // 4), max(16, s // 2), s})
