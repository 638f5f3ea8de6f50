"""The seam between ``DecodeEngine`` and a model family.

"What a token's cache entry is and which layers read it" is one
decision, and a family owns it. The engine keeps scheduling, pages,
tiers, row building, embedding, head, sampling and the read-back, and
asks a family for the members of ``Family`` below — nothing else; it
names no family. A new family is a module here, its math under
``models/`` and a line in ``FAMILIES`` (README, "Adding a family to the
serving path"). This lives under ``serving/`` because the dense
family's matmuls need ``weightplane`` and the exchange codec:
``models/`` must not import upward.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Mapping, Sequence, Tuple

from jax.sharding import PartitionSpec

from hadoop_tpu.models.config import ModelConfig


# two planes a family may need to know of, by the key they refuse under
RELAXED = "serving.parity=relaxed"
TP_PLAN = "a tp plan (serving.tp)"


class Family:
    """One per engine, built by ``family_for(cfg, asked, **options)``:
    ``asked`` maps a plane's conf key to whether the engine was asked
    for it, ``options`` are the engine's ``moe_*`` arguments, a family's
    to read or ignore. Members with a body here are optional."""

    # the pools the cache manager holds under its ONE block table, in
    # the order ``run_layers`` takes them: ``(layers, page shape)`` each,
    # given the page's tokens — a pool is ``[layers, blocks, *page
    # shape]`` in ``cfg.jax_dtype``. A pool spans the layers that read
    # it (all of them, or those of one kind), and a page holds there
    # whatever the family keeps of its tokens: an entry a token, or
    # something of a fixed size a page. ``layers`` counts SLOTS, not
    # layers of weights: a stack run several times over one set of
    # weights keeps an entry a (pass, layer).
    def pools(self, block_size: int) -> Sequence[Tuple[int, Tuple[int, ...]]]:
        raise NotImplementedError

    # per-lane state that the step carries beside tables and positions
    # (donated; an array, or a pytree of arrays, given the lanes), for a
    # family whose layers keep more of a sequence than its pages: None
    # for none. A lane that starts — cold, from a prefix hit, or resumed
    # after a preemption — has it set by ``start_lane``.
    def lane_state(self, lanes: int):
        return None

    def start_lane(self, lane, pools, slot, page):
        """``lane`` with lane ``slot``'s state that of a sequence whose
        first row here follows the last token of pool page ``page`` (the
        last page the lane maps from the prefix cache; 0, the scratch
        page, when it starts from nothing). Traced; ``slot`` and
        ``page`` are int32 scalars."""
        raise NotImplementedError

    # the (kv_heads, head_dim) pair the chain salt is made of: prefixes
    # persisted to the DFS tier are keyed by it, so it never changes
    salt_layout: Tuple[int, int]

    @property
    def page_slots(self) -> int:
        """How deep a page is where the cold tiers move it and the chain
        salt names it: the layers of weights, unless a token keeps more
        entries than the stack has layers."""
        return self.cfg.n_layers
    # every pool's spec on a tp mesh (replicated unless the family says)
    pool_spec = PartitionSpec()
    # how many local chips the expert stacks split over (0: no experts)
    expert_shards = 0
    # ServingMetrics counters fed by ``run_layers``' stats columns, in
    # order: the packed read-back is that many columns wider
    counters: Tuple[str, ...] = ()

    def __init__(self, cfg: ModelConfig, asked: Mapping[str, Any],
                 **options):
        self.cfg = cfg
        self.refuse(asked)

    def refuse(self, asked: Mapping[str, Any]) -> None:
        """Raise NotImplementedError naming the conf key of a plane
        asked for that this family is not built for (at construction,
        and again when a long-context plane is attached)."""

    def place_weights(self, params, owned: bool):
        """``params`` with the leaves ``run_layers`` reads brought, once,
        into the form its matmuls consume — in place of the loaded
        leaves, never beside them: the tree's bytes do not change.
        ``owned``: the engine holds the tree under a byte budget, so a
        replaced leaf is freed here and now, whoever else names it."""
        return params

    def place_experts(self, params):
        """(params with the expert stacks split over ``expert_shards``
        chips, the sharding the step's carried buffers then take)."""
        return params, None

    def rope_tables(self):
        """(cos, sin), traced inside the step; none by default."""
        return None, None

    def run_layers(self, params, h, pools, lane, rows: Dict[str, Any]):
        """All layers over the step's rows ``h [T, D]``: every row is
        one token at one position; scatter each live row's entry into
        the pools, then attend. ``pools``: the arrays of ``pools()``;
        ``lane``: the array (or pytree) of ``lane_state()``. ``rows``:
        ``pos``, ``blk`` (the page a row writes; scratch for a dead
        row), ``off``, ``active``, ``lens`` (``pos + 1``, 0 for a dead
        row), ``tables`` — all per row; the lanes' ``tables_s [B,
        bps]``; ``B`` lanes of ``G`` rows come first, then the chunk's
        rows, consecutive positions of lane ``chunk_slot`` of which the
        first ``chunk_n`` are live (both None: no chunk); ``block`` the
        page's tokens; ``cos``, ``sin``. Returns ``(h, pools, lane,
        stats)``, pools and lane state in their given shapes; ``stats``
        is int32 ``[len(counters)]``, or ``()`` for none."""
        raise NotImplementedError

    def count_step(self, metrics, lens, chains: Iterable, chunk_lens,
                   table: Tuple[int, int]) -> None:
        """Once a step, on the host, before dispatch: count what only
        this family has. ``lens``: the live rows' lengths, the lanes'
        then the prefill chunk's; ``chains`` yields ``(first page, pages
        held)`` per live request, lazily; ``chunk_lens``: every row of
        the prefill chunk, 0 for an inactive one (none without a chunk);
        ``table``: (tokens a page, pages a table)."""

    def describe_experts(self, rows: int) -> Dict[str, Any]:
        """What ``weight_plane()`` says of the experts beyond their
        count, shards and bytes; ``rows`` = lanes x rows a lane."""
        return {}


from hadoop_tpu.serving.families.gqa import PagedKVFamily  # noqa: E402
from hadoop_tpu.serving.families.latent import LatentFamily  # noqa: E402
from hadoop_tpu.serving.families.lfm2 import ConvStateFamily  # noqa: E402
from hadoop_tpu.serving.families.looped import LoopedKVFamily  # noqa: E402

FAMILIES: Dict[str, type] = {
    "gpt2": PagedKVFamily, "llama": PagedKVFamily,
    "mixtral": PagedKVFamily, "deepseek_v32": LatentFamily,
    "lfm2_moe": ConvStateFamily, "ouro": LoopedKVFamily}


def family_for(cfg: ModelConfig, asked: Mapping[str, Any],
               **options) -> Family:
    return FAMILIES[cfg.family](cfg, asked, **options)
