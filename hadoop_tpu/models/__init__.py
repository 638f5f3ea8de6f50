"""Model families for the TPU compute engine.

``models.decoder`` is the functional core of three families — training,
and the norm / head rules serving shares (presets: ``models.config``):

- ``gpt2``    — LayerNorm + learned positions + GeLU MLP
- ``llama``   — RMSNorm + RoPE + SwiGLU + grouped-query attention
- ``mixtral`` — llama core with a top-k routed mixture-of-experts MLP

``models.deepseek`` is a fourth, serving only (``deepseek_v32``: latent
attention, a learned sparse selection, a share of a wider router, a
stack per layer kind), ``models.lfm2`` a fifth, serving only too
(``lfm2_moe``: a gated short convolution whose state lives beside the
pages, QK-normed GQA, every expert of the router resident). The serving
step reaches every family's layers through ``serving/families``, not by
name.

Parameters are stored layer-stacked (leading ``n_layers`` dim): ``pp``
shards them over its mesh axis, one device runs them under ``lax.scan``.
"""

from hadoop_tpu.models.config import ModelConfig, PRESETS, get_config
from hadoop_tpu.models.decoder import init_params, forward, count_params

__all__ = ["ModelConfig", "PRESETS", "get_config", "init_params", "forward",
           "count_params"]
