"""In-tree model zoo for examples, benchmarks and tests.

The reference's models are external (torchvision ResNet in
``examples/imagenet``; Megatron-style GPT/BERT in
``apex/transformer/testing``); these functional equivalents keep the
framework self-contained on TPU.
"""

from apex_tpu.models.bert import (  # noqa: F401
    BertConfig,
    apply_bert,
    bert_base,
    bert_large,
    bert_tiny,
    init_bert,
    mlm_loss,
)
from apex_tpu.models.gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    apply_gpt_unsharded,
    gpt_loss_unsharded,
    gpt_medium,
    gpt_partition_specs,
    gpt_pipeline_model,
    gpt_tiny,
    gpt_to_pipeline_params,
    init_gpt,
)
from apex_tpu.models.resnet import (  # noqa: F401
    apply_resnet,
    cross_entropy_loss,
    init_resnet,
)
