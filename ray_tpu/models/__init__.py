"""Model zoo: functional JAX models designed for GSPMD sharding."""

from ray_tpu.models.llama import (
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
    llama_sharding_rules,
)
from ray_tpu.models.dit import (
    DiTConfig,
    dit_forward,
    dit_init,
    dit_loss,
    dit_sample,
    dit_sharding_rules,
)
from ray_tpu.models.jamba import JambaConfig, jamba_forward, jamba_init
from ray_tpu.models.mlp import MLPConfig, mlp_forward, mlp_init
from ray_tpu.models.vit import (
    CLIPConfig,
    CLIPTextConfig,
    ViTConfig,
    clip_encode_image,
    clip_encode_text,
    clip_init,
    clip_loss,
    clip_sharding_rules,
    vit_forward,
    vit_init,
    vit_loss,
    vit_sharding_rules,
)
