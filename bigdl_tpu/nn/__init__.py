"""bigdl_tpu.nn — module/criterion layer (the reference's ``nn`` package,
SURVEY §2.4-§2.5), re-designed for JAX."""

from bigdl_tpu.nn.module import (  # noqa: F401
    Module, Parameter, Container, Sequential, Identity, Echo,
    LayerException, functional_call, state_dict, load_state_dict,
    stamp_scope_names, capture_shapes, summary,
)
from bigdl_tpu.nn import init  # noqa: F401
from bigdl_tpu.nn.criterion import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.activation import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.linear import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.embedding import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.conv import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.pooling import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.normalization import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.shape import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.container_ext import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.rnn import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.attention import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.hyper_connection import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.linear_attention import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.short_conv import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.ssm import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.tree import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.moe import *  # noqa: F401,F403
from bigdl_tpu.nn.layers.scan import *  # noqa: F401,F403
from bigdl_tpu.nn.quantized import *  # noqa: F401,F403
from bigdl_tpu.nn.graph import Graph, Input, Node  # noqa: F401
# TF-style op subpackages stay namespaced (ops.Select vs the Select layer)
from bigdl_tpu.nn import ops  # noqa: F401
from bigdl_tpu.nn import tf  # noqa: F401
