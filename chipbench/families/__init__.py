"""Architectures, one module each, found by name: a configuration file's
``model_type`` names ``chipbench/families/<model_type>.py``. The cells'
drivers take the program's ``ModelConfig``, the weights, the plain
reference and the work a model needs from that module and from nowhere
else, so a later PR adds an architecture by adding its module and its
configuration file. What a family module defines (``model`` is the
configuration file's top-level scalars, under the source's own keys):

``model_config(model, harness)``
    the program's ``ModelConfig`` (``harness`` is the file's group of that
    name: context, capacity factor, ...).
``make_params(model, key, dtype)``
    the whole tree in the layout the program takes, from
    ``weights.seed_key(seed)``; called under ``jax.jit``. Layers need not
    share their leaves and there need not be one stack.
``layer_params(model, key, layer, dtype)``
    one layer's leaves alone (the reference regenerates a layer at a time).
``leaf_paths(model)``; ``make_leaf(model, key, path, dtype)``
    the tree's leaf paths in flatten order, and one leaf alone.
``hidden_states(model, seed, tokens, quant=None)``; ``score(model, seed, x,
positions, tokens_at, quant=None)``
    serving's plain reference: final hidden states of rows of tokens, and
    the gap of a token's logit under the best at given positions.
``follow(model, seed, batches, quant=None, keep=1.0)``
    training's: losses, first clipped gradient's and the change's norms by
    leaf, as ``reference.follow`` returns them.
``train_flops_per_token(model, seq)``; ``serve_work(model, requests)``
    operations the model *needs*: forward + backward for a token of a
    packed row, and ``{"flops": ..., "bytes": ...}`` (``bytes`` may be
    ``None``) for a list of ``Served`` requests — the contexts themselves,
    so that an attention which reads a bounded or selected part of its
    context can say what it needs.
``READERS`` (optional)
    ``reader name -> function(spec, out, cell)``: ``readers.read`` looks
    here after its own, so a family's kernel can bring the reader of its
    roofline share with its operation and byte counts in the same file.
"""

from __future__ import annotations

import importlib
import os
from dataclasses import dataclass
from typing import List


@dataclass
class Served:
    """What one request had computed for it inside the window."""
    prompt_len: int
    matched_share: float    # of the prompt, found in the prefix cache (the
    #                         window's share: the engine counts no finer)
    outputs: List[int]      # indices of its output tokens delivered in
    #                         the window; 0 is the first token, and the
    #                         prompt's prefill is counted with it


def names() -> List[str]:
    here = os.path.dirname(os.path.abspath(__file__))
    return sorted(f[:-3] for f in os.listdir(here)
                  if f.endswith(".py") and not f.startswith("_"))


def load(model: dict):
    """The module named by ``model["model_type"]``. A name without a module
    is an error that says which there are — never another architecture
    under this one's name."""
    name = model.get("model_type")
    if name not in names():
        raise SystemExit(
            f"no family for model_type {name!r}: chipbench/families/ holds "
            f"{names()}; a new architecture brings its own module there")
    return importlib.import_module(f"chipbench.families.{name}")
