"""``ouro`` on the serving path (``models/ouro.py``): the dense paged
family's layers, sandwich-normed, run ``cfg.n_passes`` times a token
over ONE set of weights. A pool is deeper than the weight stack: a token
keeps a K and a V for every (pass, layer), pass ``t`` of layer ``l`` in
slot ``t * n_layers + l``, and pass ``t`` attends to slot ``t * n_layers
+ l`` of the earlier tokens and to no other. The pass loop is a scan
around ``PagedKVFamily.run_stack`` — one layer body in the step program,
whatever the passes — with the model's final norm closing every pass but
the last, which the engine's own closes (one weight). The planes it is
not built for refuse by conf key — never a silent wrong layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hadoop_tpu.models.decoder import _norm
from hadoop_tpu.serving.families.gqa import PagedKVFamily

_NO_PATH = "pages of passes x layers slots have no such path yet"
_TIERS = ("the page movers and the tiers' files have not carried a page "
          "passes x layers slots deep")
_WHY = {
    "serving.speculate.k": "a draft row is verified after every pass of "
                           "every layer: untested at passes x layers "
                           "slots",
    "serving.kv.host.bytes": _TIERS,
    "serving.kv.dfs.enable": _TIERS,
    "serving.longctx.enable": "the long-context plane pages one K/V a "
                              "layer, not one a (pass, layer)"}


def pass_offset(t, layers: int, n_blocks: int):
    """Where pass ``t``'s slots start in a pool viewed ``[slots *
    blocks, ...]``."""
    return t * (layers * n_blocks)


class LoopedKVFamily(PagedKVFamily):
    # passes the step ran, counted by the step itself on the device
    counters = ("loop_passes",)

    def __init__(self, cfg, asked, **options):
        self.cfg = cfg
        self.refuse(asked)
        super().__init__(cfg, asked)

    def refuse(self, asked) -> None:
        for key, on in asked.items():
            if on:
                raise NotImplementedError(
                    f"family={self.cfg.family!r} does not serve under "
                    f"{key}: {_WHY.get(key, _NO_PATH)}")

    @property
    def page_slots(self) -> int:
        return self.cfg.n_passes * self.cfg.n_layers

    def pools(self, block_size):
        # the dense family's pages, in every layer of every pass
        return [(self.page_slots, page)
                for _, page in super().pools(block_size)]

    def run_layers(self, params, h, pools, lane, rows):
        cfg = self.cfg
        kp, vp = pools
        pool_shape = kp.shape
        n_blocks = pool_shape[1]
        bases = jnp.arange(cfg.n_layers, dtype=jnp.int32) * n_blocks

        def one_pass(carry, t):
            h, kc, vc, ran = carry
            h, kc, vc = self.run_stack(
                params, h, kc, vc, rows,
                bases + pass_offset(t, cfg.n_layers, n_blocks))
            with jax.named_scope("loop_norm"):
                # the last pass is closed by the engine's own final norm
                h = jnp.where(t < cfg.n_passes - 1,
                              _norm(h, params["final_norm_w"], None, cfg),
                              h)
            return (h, kc, vc, ran + 1), None

        (h, kp, vp, ran), _ = jax.lax.scan(
            one_pass,
            (h, kp.reshape((-1,) + pool_shape[2:]),
             vp.reshape((-1,) + pool_shape[2:]), jnp.int32(0)),
            jnp.arange(cfg.n_passes, dtype=jnp.int32))
        return h, (kp.reshape(pool_shape), vp.reshape(pool_shape)), lane, \
            ran[None]
