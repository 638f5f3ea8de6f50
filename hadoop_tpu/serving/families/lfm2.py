"""``lfm2_moe`` on the serving path (``models/lfm2.py``): K and V pages
in the attention layers alone, a state-tail pool in the convolution
layers (a page's tail: what a sequence that continues after that page
starts from), and the lanes' own convolution state carried with the
step. Every expert of the router is resident. The planes it is not built
for refuse by conf key — never a silent wrong layout."""

from __future__ import annotations

import numpy as np

from hadoop_tpu.models import lfm2
from hadoop_tpu.ops import rope_frequencies
from hadoop_tpu.serving.families import Family

_NO_PATH = ("the per-kind pools, the pages' state tails and the lanes' "
            "convolution state have no such path yet")
_WHY = {
    "serving.speculate.k": "a rejected draft row would have to roll the "
                           "lane's convolution state back",
    "serving.kv.host.bytes": "the page movers carry two pools, not K/V "
                             "of some layers and state tails of others",
    "serving.kv.dfs.enable": "the page movers carry two pools, not K/V "
                             "of some layers and state tails of others",
    "serving.longctx.enable": "the long-context plane pages per-head K/V "
                              "in every layer"}


class ConvStateFamily(Family):
    counters = lfm2.COUNTERS
    expert_shards = 1       # every expert here, none split over chips

    def __init__(self, cfg, asked, **options):
        # no cold tier: the page is only a layout to salt the chain with
        self.salt_layout = (cfg.n_kv_heads, cfg.head_dim)
        super().__init__(cfg, asked)

    def refuse(self, asked) -> None:
        for key, on in asked.items():
            if on:
                raise NotImplementedError(
                    f"family={self.cfg.family!r} does not serve under "
                    f"{key}: {_WHY.get(key, _NO_PATH)}")

    def pools(self, block_size):
        cfg = self.cfg
        s = lfm2.state_rows(cfg)
        if block_size < s:
            raise ValueError(
                f"serving.kv.block.size={block_size} is below the "
                f"{s} tokens a convolution layer carries: a page's tail "
                "would reach into the page before it")
        # a token's KV heads side by side in one row of the page
        kv = (lfm2.n_ops(cfg, "full_attention"),
              (block_size, cfg.n_kv_heads * cfg.head_dim))
        return [kv, kv, (lfm2.n_ops(cfg, "conv"), (s, cfg.d_model))]

    def lane_state(self, lanes):
        cfg = self.cfg
        return (lfm2.n_ops(cfg, "conv"), lanes, lfm2.state_rows(cfg),
                cfg.d_model)

    def start_lane(self, lane, pools, slot, page):
        return lfm2.start_lane(lane, pools[2], slot, page)

    def rope_tables(self):
        return rope_frequencies(self.cfg.head_dim, self.cfg.max_seq,
                                self.cfg.rope_theta)

    def run_layers(self, params, h, pools, lane, rows):
        return lfm2.run_layers(params, h, pools, lane, self.cfg, rows)

    def count_step(self, metrics, lens, chains) -> None:
        cfg = self.cfg
        metrics.moe_assignments.incr(
            int(np.size(lens)) * cfg.top_k
            * (cfg.n_layers - cfg.n_dense_layers))

    def describe_experts(self, rows: int):
        return {"experts_routed": self.cfg.n_routed_experts,
                "experts_from": self.cfg.experts_from}
