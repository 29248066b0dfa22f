"""Config registry: ``--arch <id>`` resolution for every assigned
architecture plus the paper's own CNN.
"""

from repro.configs.base import ModelConfig, param_count  # noqa: F401

from repro.configs import (whisper_base, deepseek_v2_236b,
                           deepseek_v2_lite, zamba2_7b,
                           smollm_135m, minitron_8b, falcon_mamba_7b,
                           qwen3_14b, qwen2_72b, paligemma_3b,
                           granite_moe_3b_a800m, lenet_mnist)

ARCHS: dict[str, ModelConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (whisper_base, deepseek_v2_236b, deepseek_v2_lite, zamba2_7b,
              smollm_135m,
              minitron_8b, falcon_mamba_7b, qwen3_14b, qwen2_72b,
              paligemma_3b, granite_moe_3b_a800m, lenet_mnist)
}

ASSIGNED = [n for n in ARCHS if n != "lenet-mnist"]


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise ValueError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]
