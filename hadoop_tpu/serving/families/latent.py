"""``deepseek_v32`` on the serving path (``models/deepseek.py``): a
token's entry is one latent (the K-slot pool) and one index key (the
V-slot pool) under one block table; per-kind layer stacks, a scan per
run of like layers; one chip's share of a wider router, so nothing of
the experts is split over this replica's devices. The planes it is not
built for refuse by conf key — never a silent wrong layout."""

from __future__ import annotations

import numpy as np

from hadoop_tpu.models import deepseek
from hadoop_tpu.serving.families import Family

_NO_PATH = ("the latent/index-key page layout, the sparse selection and "
            "the held-expert layer have no such path yet")
_WHY = {"serving.longctx.enable": "the long-context plane pages per-head "
                                  "K/V, not latents and index keys"}


class LatentFamily(Family):
    # a step's assignments to held experts, and held experts hit
    counters = ("moe_assignments_local", "moe_local_experts_hit")
    expert_shards = 1       # one chip's share of a wider router

    def __init__(self, cfg, asked, **options):
        # no cold tier: the page is only a layout to salt the chain with
        self.salt_layout = (1, deepseek.latent_width(cfg)
                            + cfg.index_head_dim)
        super().__init__(cfg, asked)

    def pools(self, block_size):
        # a token's latent, and its index key, in every layer
        cfg = self.cfg
        return [(cfg.n_layers, (block_size, deepseek.latent_width(cfg))),
                (cfg.n_layers, (block_size, cfg.index_head_dim))]

    def refuse(self, asked) -> None:
        for key, on in asked.items():
            if on:
                raise NotImplementedError(
                    f"family={self.cfg.family!r} does not serve under "
                    f"{key}: {_WHY.get(key, _NO_PATH)}")

    def rope_tables(self):
        return deepseek.rope_tables(self.cfg)

    def run_layers(self, params, h, pools, lane, rows):
        # table-sharing groups: a lane's rows, then the chunk's rows
        b, g = rows["B"], rows["G"]
        tables_s, lens = rows["tables_s"], rows["lens"]
        groups = [(0, tables_s, lens[:b * g].reshape(b, g))]
        if rows["chunk_slot"] is not None:
            groups.append((b * g, tables_s[rows["chunk_slot"]][None, :],
                           lens[b * g:][None, :]))
        h, kp, vp, stats = deepseek.run_layers(
            params, h, *pools, self.cfg, {**rows, "groups": groups})
        return h, (kp, vp), lane, stats

    def count_step(self, metrics, lens, chains) -> None:
        # the sparse selection: entries the live rows could attend to
        # against the entries they keep (a layer; every layer alike)
        cfg = self.cfg
        metrics.attn_entries_live.incr(int(np.sum(lens)))
        metrics.attn_entries_selected.incr(
            int(np.sum(np.minimum(lens, cfg.index_topk))))
        metrics.moe_assignments.incr(
            int(lens.size) * cfg.top_k
            * (cfg.n_layers - cfg.n_dense_layers))
        # distinct pages under those rows, from below: requests whose
        # tables start with the same page share a radix chain; the
        # longest of them alone holds that many pages
        longest = {}
        for first, pages in chains:
            longest[first] = max(longest.get(first, 0), pages)
        metrics.attn_pages_distinct.incr(sum(longest.values()))

    def describe_experts(self, rows: int):
        return {"experts_routed": self.cfg.n_routed_experts,
                "experts_from": self.cfg.experts_from}
