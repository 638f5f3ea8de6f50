"""``family="ouro"``: a looped decoder. ``cfg.n_layers`` llama layers —
RMSNorm, RoPE, causal attention, SwiGLU — each with a norm AFTER its two
sub-layers as well as before them (``cfg.sandwich_norm``), run
``cfg.n_passes`` times a token over one set of weights::

    x = embed(tokens)
    for t in range(n_passes):
        for l in range(n_layers):
            x += norm(Attn_l(norm(x)))      # K, V cached in slot t * n_layers + l
            x += norm(MLP_l(norm(x)))
        x = final_norm(x)                   # closes every pass
    logits = head(x)

Pass ``t`` attends to what pass ``t`` of the earlier tokens cached, and
to nothing else: a token keeps ``n_passes * n_layers`` K/V entries. An
exit gate ``sigmoid(x @ exit_gate_w + exit_gate_b)`` after each pass
gives the passes a distribution; at ``early_exit_threshold`` 1.0 (the
only one ``ModelConfig`` admits) every token takes the last pass, so the
gate's two leaves are in the tree and enter no logit.

The layer body is the dense serving family's (``serving/families/gqa.py``,
its two optional norms); the pass loop, the slots and the pools' depth
are ``serving/families/looped.py``. The tree is ``decoder.init_params``'s
plus ``layers.attn_post_norm_w``, ``layers.mlp_post_norm_w``,
``exit_gate_w`` and ``exit_gate_b``.

Training is not built for this family (``models.config.refuse_training``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from hadoop_tpu.models import decoder
from hadoop_tpu.models.config import ModelConfig


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    """A random tree in the layout the engine takes (tests, smoke runs).
    Norm weights are drawn about 1, not set to it: an all-ones weight
    would hide a norm that was dropped or taken from another place."""
    k_dense, k_norms, k_gate = jax.random.split(rng, 3)
    dt = cfg.jax_dtype
    dense = dataclasses.replace(cfg, family="llama", n_passes=1,
                                sandwich_norm=False)
    params = decoder.init_params(k_dense, dense)
    L, D = cfg.n_layers, cfg.d_model
    names = ("attn_norm_w", "attn_post_norm_w", "mlp_norm_w",
             "mlp_post_norm_w")
    keys = jax.random.split(k_norms, len(names) + 1)
    for key, name in zip(keys, names):
        params["layers"][name] = (
            1.0 + 0.05 * jax.random.normal(key, (L, D))).astype(dt)
    params["final_norm_w"] = (
        1.0 + 0.05 * jax.random.normal(keys[-1], (D,))).astype(dt)
    params["exit_gate_w"] = (jax.random.normal(k_gate, (D, 1), jnp.float32)
                             * D ** -0.5).astype(dt)
    params["exit_gate_b"] = jnp.zeros((1,), dt)
    return params
