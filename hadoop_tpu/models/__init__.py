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
pages, QK-normed GQA, every expert of the router resident),
``models.ouro`` a sixth, serving only (``ouro``: the llama layer with a
norm after each sub-layer too, the stack run several times a token over
one set of weights). The serving step reaches every family's layers
through ``serving/families``, not by name; ``init_params_for`` gives the
function that makes a family's tree (what a checkpoint is loaded
against).

Parameters are stored layer-stacked (leading ``n_layers`` dim): ``pp``
shards them over its mesh axis, one device runs them under ``lax.scan``.
"""

from hadoop_tpu.models.config import ModelConfig, PRESETS, get_config
from hadoop_tpu.models.decoder import init_params, forward, count_params



def init_params_for(cfg: ModelConfig):
    """``init_params(rng, cfg)`` of ``cfg.family``: the decoder's, or the
    module's of a family that has its own tree."""
    import importlib
    own = {"deepseek_v32": "deepseek", "lfm2_moe": "lfm2", "ouro": "ouro"}
    if cfg.family not in own:
        return init_params
    return importlib.import_module(
        f"hadoop_tpu.models.{own[cfg.family]}").init_params


__all__ = ["ModelConfig", "PRESETS", "get_config", "init_params",
           "init_params_for", "forward", "count_params"]
