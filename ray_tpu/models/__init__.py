from ray_tpu.models import transformer, vit
from ray_tpu.models.gpt2 import gpt2_config
from ray_tpu.models.granite import granite_config
from ray_tpu.models.kimi_vl import kimi_vl_config
from ray_tpu.models.llama import llama_config
from ray_tpu.models.mellum import mellum_config
from ray_tpu.models.mixtral import mixtral_config
from ray_tpu.models.ouro import ouro_config
from ray_tpu.models.solar_open2 import solar_open2_config
from ray_tpu.models.trinity import trinity_config
from ray_tpu.models.transformer import KDAConfig, MoEConfig, SSMConfig, TransformerConfig
from ray_tpu.models.vit import ViTConfig, vit_config

__all__ = [
    "KDAConfig",
    "MoEConfig",
    "SSMConfig",
    "TransformerConfig",
    "ViTConfig",
    "gpt2_config",
    "granite_config",
    "kimi_vl_config",
    "llama_config",
    "mellum_config",
    "mixtral_config",
    "ouro_config",
    "solar_open2_config",
    "transformer",
    "trinity_config",
    "vit",
    "vit_config",
]
