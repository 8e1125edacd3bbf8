"""automodel_tpu: a TPU-native (JAX/XLA/Pallas/pjit) training framework.

Capabilities modeled on NVIDIA NeMo AutoModel (see SURVEY.md): YAML-recipe-driven
fine-tuning and pretraining of Hugging Face LLMs/VLMs, where parallelism is pure
configuration over a single ``jax.sharding.Mesh`` (FSDP/HSDP, TP+SP, PP, ring-attention
CP, and EP), with day-0 HF checkpoint interop via safetensors state-dict adapters.

Top-level exports are lazy so that importing the package stays cheap
(reference: nemo_automodel/__init__.py:25-36).
"""

__version__ = "0.1.0"

_LAZY = {
    "ConfigNode": "automodel_tpu.config.loader",
    "instantiate": "automodel_tpu.config.loader",
    "load_config": "automodel_tpu.config.loader",
    "parse_args_and_load_config": "automodel_tpu.config.cli_overrides",
    "MeshContext": "automodel_tpu.parallel.mesh",
    "create_device_mesh": "automodel_tpu.parallel.mesh",
    "AutoModelForCausalLM": "automodel_tpu.models.auto",
    "AutoTokenizer": "automodel_tpu.models.auto_tokenizer",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        mod = importlib.import_module(_LAZY[name])
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals().keys()) + list(_LAZY.keys()))
