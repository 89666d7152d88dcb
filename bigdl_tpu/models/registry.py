"""Model-zoo registry: one table mapping model names to builders and
canonical input specs.

``models/cli.py`` (train/test/perf entry points) and the static analyzer
(``python -m bigdl_tpu.analysis <model>``) both resolve names here, so a
model added to the zoo is automatically runnable *and* checkable.  The
``input_spec`` is the abstract ``ShapeDtypeStruct`` the shape pass feeds
``jax.eval_shape`` — no data, no compile.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple

__all__ = ["ModelEntry", "MODELS", "LANGUAGE_MODELS", "model_names",
           "build_model", "input_spec", "train_pieces"]


class ModelEntry(NamedTuple):
    #: num_classes -> model (0/None means the builder's own default)
    build: Callable[[int], Any]
    #: batch -> (pytree of) jax.ShapeDtypeStruct
    spec: Callable[[int], Any]


def _img(c: int, h: int, w: int):
    def make(batch: int = 2):
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct((batch, c, h, w), jnp.float32)

    return make


def _flat(n: int):
    def make(batch: int = 2):
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct((batch, n), jnp.float32)

    return make


def _tokens(seq_len: int):
    def make(batch: int = 2):
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)

    return make


def _b(fn_name: str):
    def build(num_classes: int = 0):
        from bigdl_tpu import models

        fn = getattr(models, fn_name)
        return fn(num_classes) if num_classes else fn()

    return build


#: sequence lengths matching models/cli.py's data pipeline
LSTM_SEQ_LEN = 200
LM_SEQ_LEN = 128
LSTM_VOCAB = 5000
#: dlrm feature geometry (models/dlrm.py defaults): 13 count features +
#: 8 categorical ids, one per table
DLRM_FEATURES = 13 + 8
DLRM_VOCAB = 50000


def _resnet_cifar(num_classes: int = 0):
    from bigdl_tpu import models

    return models.build_resnet_cifar(20, num_classes or 10)


def _resnet50(num_classes: int = 0):
    from bigdl_tpu import models

    return models.build_resnet(50, num_classes or 1000)


def _autoencoder(num_classes: int = 0):
    from bigdl_tpu import models

    return models.build_autoencoder()


def _lstm(num_classes: int = 0):
    from bigdl_tpu import models

    return models.build_lstm_classifier(LSTM_VOCAB,
                                        class_num=num_classes or 2)


def _transformer(num_classes: int = 0):
    from bigdl_tpu import models

    return models.build_transformer_lm(vocab_size=num_classes or 256)


def _decoder_lm(num_classes: int = 0):
    from bigdl_tpu import models

    return models.build_decoder_lm(
        models.tiny_decoder_plan(num_classes or 256))


def _dlrm(num_classes: int = 0):
    from bigdl_tpu import models

    return models.build_dlrm(class_num=num_classes or 2)

MODELS: Dict[str, ModelEntry] = {
    "lenet": ModelEntry(_b("build_lenet5"), _flat(28 * 28)),
    "vgg16": ModelEntry(_b("build_vgg16"), _img(3, 224, 224)),
    "vgg19": ModelEntry(_b("build_vgg19"), _img(3, 224, 224)),
    "vgg_cifar": ModelEntry(_b("build_vgg_for_cifar10"),
                            _img(3, 32, 32)),
    "inception_v1": ModelEntry(_b("build_inception_v1"),
                               _img(3, 224, 224)),
    "inception_v2": ModelEntry(_b("build_inception_v2"),
                               _img(3, 224, 224)),
    "resnet": ModelEntry(_resnet_cifar, _img(3, 32, 32)),
    "resnet50": ModelEntry(_resnet50, _img(3, 224, 224)),
    "autoencoder": ModelEntry(_autoencoder, _flat(28 * 28)),
    "lstm": ModelEntry(_lstm, _tokens(LSTM_SEQ_LEN)),
    "transformer": ModelEntry(_transformer, _tokens(LM_SEQ_LEN)),
    # a current decoder from a per-layer plan (models/transformer.py
    # build_decoder_lm) at a tiny plan; trains like "transformer"
    "decoder_lm": ModelEntry(_decoder_lm, _tokens(LM_SEQ_LEN)),
    # recsys ranking (models/dlrm.py): [batch, 13 count + 8 categorical]
    # int32 features -> click log-probs; the sparse-sync proof shape
    "dlrm": ModelEntry(_dlrm, _tokens(DLRM_FEATURES)),
}


def model_names():
    return sorted(MODELS)


def build_model(name: str, num_classes: int = 0):
    if name not in MODELS:
        raise KeyError(f"unknown model {name!r}; choose from "
                       f"{model_names()}")
    return MODELS[name].build(num_classes)


def input_spec(name: str, batch: int = 2):
    if name not in MODELS:
        raise KeyError(f"unknown model {name!r}; choose from "
                       f"{model_names()}")
    return MODELS[name].spec(batch)


#: next-token language models: [batch, seq] ids in, log-probs per position
#: out, trained with a time-distributed ClassNLL averaged over positions
LANGUAGE_MODELS = ("transformer", "decoder_lm")

#: models whose output is ClassNLL-compatible (log-probs over classes,
#: integer labels).  A model in MODELS but not here (and not special-
#: cased below) makes train_pieces return None — the attribution CLI
#: then falls back to forward-only rather than lowering a nonsense step.
_CLASSIFIERS = frozenset({
    "lenet", "vgg16", "vgg19", "vgg_cifar", "inception_v1",
    "inception_v2", "resnet", "resnet50", "lstm", "dlrm",
})


def train_pieces(name: str, batch: int = 2):
    """``(criterion, target ShapeDtypeStruct)`` for training this model
    on synthetic specs — what the cost-attribution CLI needs to lower a
    full TrainStep without data (``telemetry/attribution.py``).  Returns
    None for models the table doesn't know how to train."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn

    if name not in MODELS:
        raise KeyError(f"unknown model {name!r}; choose from "
                       f"{model_names()}")
    if name == "autoencoder":
        return (nn.MSECriterion(),
                jax.ShapeDtypeStruct((batch, 28 * 28), jnp.float32))
    if name in LANGUAGE_MODELS:
        return (nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                            size_average=True),
                jax.ShapeDtypeStruct((batch, LM_SEQ_LEN), jnp.int32))
    if name in _CLASSIFIERS:
        return (nn.ClassNLLCriterion(),
                jax.ShapeDtypeStruct((batch,), jnp.int32))
    return None
