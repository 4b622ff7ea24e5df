"""Model zoo: TPU-first flax implementations with logical-axis sharding
annotations consumed by ``ray_tpu.parallel.sharding``."""

from ray_tpu.models.afmoe import AFMoE, AFMoEConfig  # noqa: F401
from ray_tpu.models.deepseek_v3 import (  # noqa: F401
    DeepseekV3,
    DeepseekV3Config,
)
from ray_tpu.models.gpt2 import GPT2, GPT2Config  # noqa: F401
from ray_tpu.models.hyper import (  # noqa: F401
    Coefficients,
    Connection,
    residual,
)
from ray_tpu.models.llama import Llama, LlamaConfig  # noqa: F401
from ray_tpu.models.nemotron_h import (  # noqa: F401
    NemotronH,
    NemotronHConfig,
)
from ray_tpu.models.moe import (  # noqa: F401
    MoEConfig,
    MoETransformer,
    SparseMoEMLP,
)
from ray_tpu.models.resnet import ResNet, ResNetConfig  # noqa: F401
from ray_tpu.models.vit import ViT, ViTConfig  # noqa: F401
