"""bigdl_tpu.models — model zoo (SURVEY §2.13).

``models.registry`` maps zoo names to builders + canonical input specs;
it backs both the train/test/perf CLI (``models/cli.py``) and the static
analyzer (``python -m bigdl_tpu.analysis <name>``).
"""

from bigdl_tpu.models import registry  # noqa: F401

from bigdl_tpu.models.autoencoder import build_autoencoder  # noqa: F401
from bigdl_tpu.models.dlrm import build_dlrm  # noqa: F401
from bigdl_tpu.models.inception import (  # noqa: F401
    build_inception_v1, build_inception_v2, inception_layer_v1,
)
from bigdl_tpu.models.lenet import build_lenet5  # noqa: F401
from bigdl_tpu.models.resnet import build_resnet, build_resnet_cifar  # noqa: F401
from bigdl_tpu.models.rnn import build_lstm_classifier, build_simple_rnn  # noqa: F401
from bigdl_tpu.models.transformer import (  # noqa: F401
    DecoderPlan, LayerPlan, build_decoder_lm, build_transformer_lm,
    tiny_decoder_plan)
from bigdl_tpu.models.vgg import (  # noqa: F401
    build_vgg16, build_vgg19, build_vgg_for_cifar10,
)
