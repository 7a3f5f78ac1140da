"""Model zoo used by the examples, benchmarks and the graft entry.

The reference (jithunnair-amd/apex) ships models only inside examples/tests
(ResNet-50 in ``examples/imagenet/main_amp.py``, DCGAN in ``examples/dcgan``,
toy MLPs in ``tests/L0``); its contrib MHA targets transformer encoders.
This package holds TPU-native functional implementations of those workloads
(transformer today; ResNet/DCGAN as they land) so the BASELINE configs are
runnable end-to-end without external model code.
"""
from .transformer import (TransformerConfig, transformer_init,
                          transformer_apply, transformer_loss,
                          transformer_pspecs, bert_large_config)
from .resnet import (ResNetConfig, resnet18_config, resnet50_config,
                     resnet_init, resnet_apply)
from .dcgan import (DCGANConfig, dcgan_init, generator_apply,
                    discriminator_apply)
from .moe_transformer import (MoETransformerConfig, moe_transformer_init,
                              moe_transformer_apply, moe_transformer_loss)
from .lfm2 import (Lfm2Config, lfm2_24b_a2b_config, lfm2_cut_layer_types,
                   lfm2_init, lfm2_apply, lfm2_loss, lfm2_routing)
from .nemotron_h import (NemotronHConfig, nemotron3_super_120b_a12b_config,
                         nemotron_h_cut_pattern, nemotron_h_init,
                         nemotron_h_share, nemotron_h_apply, nemotron_h_loss,
                         nemotron_h_routing)
from .qwen3_next import (Qwen3NextConfig, qwen3_next_80b_a3b_config,
                         qwen3_next_init, qwen3_next_share, qwen3_next_apply,
                         qwen3_next_loss, qwen3_next_routing)
from .glm4_moe_lite import (Glm4MoeLiteConfig, glm47_flash_config,
                            glm4_moe_lite_init, glm4_moe_lite_share,
                            glm4_moe_lite_apply, glm4_moe_lite_loss,
                            glm4_moe_lite_routing)
from .laguna import (LagunaConfig, laguna_s_2_1_config, laguna_init,
                     laguna_share, laguna_apply, laguna_loss, laguna_routing)

__all__ = [
    "TransformerConfig", "transformer_init", "transformer_apply",
    "transformer_loss", "transformer_pspecs", "bert_large_config",
    "ResNetConfig", "resnet18_config", "resnet50_config", "resnet_init",
    "resnet_apply",
    "DCGANConfig", "dcgan_init", "generator_apply", "discriminator_apply",
    "MoETransformerConfig", "moe_transformer_init", "moe_transformer_apply",
    "moe_transformer_loss",
    "Lfm2Config", "lfm2_24b_a2b_config", "lfm2_cut_layer_types", "lfm2_init",
    "lfm2_apply", "lfm2_loss", "lfm2_routing",
    "NemotronHConfig", "nemotron3_super_120b_a12b_config",
    "nemotron_h_cut_pattern", "nemotron_h_init", "nemotron_h_share",
    "nemotron_h_apply", "nemotron_h_loss", "nemotron_h_routing",
    "Qwen3NextConfig", "qwen3_next_80b_a3b_config", "qwen3_next_init",
    "qwen3_next_share", "qwen3_next_apply", "qwen3_next_loss",
    "qwen3_next_routing",
    "Glm4MoeLiteConfig", "glm47_flash_config", "glm4_moe_lite_init",
    "glm4_moe_lite_share", "glm4_moe_lite_apply", "glm4_moe_lite_loss",
    "glm4_moe_lite_routing",
    "LagunaConfig", "laguna_s_2_1_config", "laguna_init", "laguna_share",
    "laguna_apply", "laguna_loss", "laguna_routing",
]
