"""Flagship training throughput on one TPU chip, measured in this process.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N,
   "device": {"platform": "tpu", "kind": ..., "count": N}, ...}

Metric: training tokens/sec/chip on the flagship llama-family model
(fwd+bwd+AdamW, bf16, jit). ``vs_baseline`` is measured MFU divided by
0.45 — the Megatron-LM-class MFU the reference metadata names as its
north star ("match H100 Megatron-LM MFU", BASELINE.json). The reference
tree itself publishes no numbers (BASELINE.md), so the baseline is that
published target utilization, making vs_baseline hardware-neutral.

There is no fallback: without a TPU, or on a TPU whose peak is not in
the table below, the script fails instead of printing a rate. The
defaults are the one configuration with a chip record (BENCH_LOG.jsonl,
2026-07-31): flagship-1b, batch 2 x seq 2048, selective ("dots") remat —
batch 4 does not fit 16 GB beside the ~12 GB of params + grads + f32
AdamW state.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# Per-chip peak bf16 FLOP/s keyed by jax's ``device_kind`` ("TPU v5
# lite" is what jax 0.9.0 reports for a v5e). Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16). A device that is not
# here is an error, not a default: add it with its source.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak recorded for device_kind {device_kind!r}; add "
            f"it to bench.PEAK_BF16_FLOPS with its source (known: "
            f"{sorted(PEAK_BF16_FLOPS)})") from None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="flagship-1b")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--remat", default="dots",
                    choices=["none", "full", "dots"])
    args = ap.parse_args()
    remat = {"none": False, "full": True, "dots": "dots"}[args.remat]

    import jax

    from hadoop_tpu.util.jaxcache import configure_compile_cache
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py measures a TPU; jax found platform="
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    peak = peak_flops(dev.device_kind)

    import jax.numpy as jnp
    from hadoop_tpu.models import count_params, get_config
    from hadoop_tpu.parallel import MeshPlan, make_mesh
    from hadoop_tpu.parallel.train import (init_sharded, make_data_sharding,
                                           make_train_step)

    cfg = get_config(args.preset, max_seq=args.seq)
    plan = MeshPlan()  # single chip
    mesh = make_mesh(plan)
    step = make_train_step(cfg, plan, mesh, remat=remat, donate=True)
    params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan, mesh)
    n_params = count_params(params)

    ds = make_data_sharding(mesh)
    key = jax.random.PRNGKey(1)
    tokens = jax.device_put(
        jax.random.randint(key, (args.batch, args.seq), 0, cfg.vocab_size,
                           dtype=jnp.int32), ds)
    targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)

    # The steps chain on donated buffers, so one sync on the last step's
    # outputs bounds the whole timed region.
    for _ in range(args.warmup):
        params, opt, metrics = step(params, opt, tokens, targets)
    jax.block_until_ready((params, opt, metrics))

    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt, metrics = step(params, opt, tokens, targets)
    jax.block_until_ready((params, opt, metrics))
    dt = time.perf_counter() - t0

    tokens_per_step = args.batch * args.seq
    tok_s = tokens_per_step * args.steps / dt
    # fwd+bwd matmul FLOPs: 6*N per token + causal attention term
    flops_per_token = 6 * n_params + 12 * cfg.n_layers * args.seq * \
        cfg.d_model // 2
    mfu = tok_s * flops_per_token / peak

    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "preset": args.preset,
        "n_params": n_params,
        "batch": args.batch,
        "seq": args.seq,
        "steps": args.steps,
        "remat": args.remat,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "loss": round(float(metrics["loss"]), 4),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
