from automodel_tpu.models.falcon_h1.model import FalconH1Config, FalconH1ForCausalLM

__all__ = ["FalconH1Config", "FalconH1ForCausalLM"]
