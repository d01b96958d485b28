"""Model zoo built on the layer DSL (reference: v1_api_demo/model_zoo,
benchmark/paddle/image + rnn configs)."""

from paddle_tpu.models import lenet
from paddle_tpu.models import alexnet
from paddle_tpu.models import resnet
from paddle_tpu.models import text_lstm
from paddle_tpu.models import seq2seq
from paddle_tpu.models import deepfm
from paddle_tpu.models import gan
from paddle_tpu.models import vae
from paddle_tpu.models import sequence_tagging
from paddle_tpu.models import srl
from paddle_tpu.models import transformer
from paddle_tpu.models import glm_moe_lite
from paddle_tpu.models import qwen3_next
from paddle_tpu.models import quick_start
from paddle_tpu.models import traffic_prediction
from paddle_tpu.models import googlenet
from paddle_tpu.models import smallnet
