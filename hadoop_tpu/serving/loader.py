"""Serving-side checkpoint load: DFS path → decoder params.

Reads the trainer's sharded checkpoints (``parallel.checkpoint`` layout:
``step_N/manifest.json`` + ``shard_*.bin``) straight off any FileSystem —
for a ``DistributedFileSystem`` the shard reads ride the client's hedged
read pool (``dfs.client.hedged.read.*``), so one slow DataNode doesn't
stall replica startup, exactly the straggler story the trainer already
gets for input data. Shards are fetched CONCURRENTLY through a bounded
worker pool (``serving.loader.io.workers``): replica cold-start is pure
IO fan-in latency, and sequential shard pulls were paying one
round-trip per shard file.

The trainer persists ``{"params": ..., "opt": ...}``; serving wants the
params only. The manifest's leaf names tell us which layout we're
looking at, so both wrapped trees and bare param trees load — and the
optimizer shards are never even read.
"""

from __future__ import annotations

import json
import logging
import time
from typing import Optional, Tuple

import jax

from hadoop_tpu.models import init_params_for
from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.parallel.checkpoint import latest_step, load_checkpoint

log = logging.getLogger(__name__)

HEDGED_POOL_KEY = "dfs.client.hedged.read.threadpool.size"
HEDGED_THRESHOLD_KEY = "dfs.client.hedged.read.threshold"
IO_WORKERS_KEY = "serving.loader.io.workers"


def serving_read_defaults(conf) -> None:
    """Arm hedged reads for checkpoint pulls unless the deployment
    already chose: replica startup is latency-critical fan-in from many
    DataNodes, the canonical hedged-read shape."""
    conf.set_if_unset(HEDGED_POOL_KEY, "4")
    conf.set_if_unset(HEDGED_THRESHOLD_KEY, "0.5")


def load_serving_params(fs, base_dir: str, cfg: ModelConfig, *,
                        step: Optional[int] = None,
                        mesh=None, specs=None,
                        io_workers: int = 4,
                        leaf_transform=None) -> Tuple[dict, int]:
    """Load decoder params for ``cfg`` from ``base_dir`` on ``fs``.

    Returns ``(params, step)``. With ``mesh`` + ``specs`` the leaves are
    placed sharded (the engine passes ``param_specs`` when it owns a
    mesh). ``io_workers`` bounds the concurrent shard fetches (1 =
    sequential). ``leaf_transform`` switches ``load_checkpoint`` to its
    streaming per-leaf mode — the weight plane's quantize-at-load seam
    (``serving/weightplane.py``): each assembled leaf is consumed the
    moment its shards arrive, so the full f32 model is never resident
    on the host. Raises FileNotFoundError when no complete checkpoint
    exists.
    """
    t0 = time.monotonic()
    if step is None:
        step = latest_step(fs, base_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {base_dir}")
    manifest = json.loads(fs.read_all(
        f"{base_dir}/step_{step:012d}/manifest.json").decode())
    shapes = jax.eval_shape(lambda k: init_params_for(cfg)(k, cfg),
                            jax.random.PRNGKey(0))
    wrapped = any(name.startswith("['params']")
                  for name in manifest["leaves"])
    like = {"params": shapes} if wrapped else shapes
    spec_tree = {"params": specs} if (wrapped and specs is not None) \
        else specs
    tree, step = load_checkpoint(fs, base_dir, like, step=step,
                                 mesh=mesh, specs=spec_tree,
                                 io_workers=max(1, io_workers),
                                 leaf_transform=leaf_transform)
    params = tree["params"] if wrapped else tree
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log.info("loaded %d-param checkpoint step %d from %s in %.2fs "
             "(%d io workers)", n, step, base_dir,
             time.monotonic() - t0, max(1, io_workers))
    return params, step
