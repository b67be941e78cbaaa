"""Architecture configs (one module per assigned architecture)."""
from .base import ModelConfig, get_config, list_configs, register, scale_down

_LOADED = False


_ARCH_MODULES = ("deepseek_coder_33b", "deepseek_v2_lite", "internvl2_26b",
                 "jamba_v01_52b", "kimi_k2_1t_a32b", "mistral_large_123b",
                 "mixtral_8x22b", "qwen2_1_5b", "qwen3_8b", "rwkv6_3b",
                 "seamless_m4t_medium")


def _load_all() -> None:
    global _LOADED
    if _LOADED:
        return
    import importlib
    for mod in _ARCH_MODULES:       # import for the register() side effect
        importlib.import_module(f".{mod}", __name__)
    _LOADED = True


__all__ = ["ModelConfig", "get_config", "list_configs", "register",
           "scale_down"]
