"""``model_type: mixtral``: the Mistral decoder with ``num_local_experts``
routed SwiGLU experts in place of the MLP — one module serves both names
(``mistral.py`` branches on the expert count)."""

from chipbench.families.mistral import (  # noqa: F401
    follow, hidden_states, layer_params, leaf_paths, make_leaf, make_params,
    model_config, score, serve_work, train_flops_per_token)
