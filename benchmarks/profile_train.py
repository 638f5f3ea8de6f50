"""Component-level timing of the flagship train step (diagnosis tool).

Times forward-only, fwd+bwd, and the full optimizer step separately at
several batch sizes to locate super-linear scaling, and — the overlap
round's additions — times the step with the communication-overlap pass
on vs off (``--overlap both``) and breaks out checkpointing into its
blocking (host-snapshot) and background (DFS write) halves
(``--ckpt both``). On a single-device plan the A-B delta is compile
noise by construction (the pass only changes collectives); on a
multichip plan it is the recovered communication time.

``--parity both`` adds the relaxed-tier rung (parallel/lowp):
quantized collective payloads + the true chunked collective matmul,
timed beside the bitwise step, with the trace-time comm-byte ledger
(payload vs reference bytes per step) in the row — so every future
run of the ladder prices BOTH tiers. ``--guard-steps N`` additionally
runs the loss-curve A-B acceptance over N training steps and records
the verdict in the JSON.

  python -m benchmarks.profile_train --preset tiny --seq 512 \
      --dp 2 --tp 2 --overlap both --ckpt both --parity both
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from hadoop_tpu.models import count_params, get_config
from hadoop_tpu.parallel import MeshPlan, make_mesh
from hadoop_tpu.parallel.overlap import DEFAULT_OVERLAP, OVERLAP_OFF
from hadoop_tpu.parallel.train import (init_sharded, make_data_sharding,
                                       make_train_step)


def timeit(fn, *args, steps=8, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def ckpt_breakdown(params, opt, mode: str) -> dict:
    """Blocking vs background checkpoint cost on a local FileSystem.

    sync_ms: the whole old-style save (what the step loop used to eat).
    snapshot_ms: the device→host copy — ALL an async save blocks for.
    write_ms: the DFS write the background thread absorbs.
    """
    import shutil
    import tempfile

    from hadoop_tpu.fs import FileSystem
    from hadoop_tpu.parallel.checkpoint import (AsyncCheckpointWriter,
                                                save_checkpoint,
                                                snapshot_tree,
                                                write_snapshot)
    out: dict = {}
    td = tempfile.mkdtemp(prefix="profile-ckpt-")
    try:
        fs = FileSystem.get(f"file://{td}")
        tree = {"params": params, "opt": opt}
        if mode in ("sync", "both"):
            t0 = time.perf_counter()
            save_checkpoint(fs, f"{td}/sync", 1, tree)
            out["sync_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        if mode in ("async", "both"):
            t0 = time.perf_counter()
            snap = snapshot_tree(tree)
            t1 = time.perf_counter()
            out["snapshot_ms"] = round((t1 - t0) * 1e3, 2)
            writer = AsyncCheckpointWriter()
            writer.submit(lambda: write_snapshot(fs, f"{td}/async", 1,
                                                 snap))
            writer.wait()
            out["write_ms"] = round((time.perf_counter() - t1) * 1e3, 2)
    finally:
        shutil.rmtree(td, ignore_errors=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="flagship-420m")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batches", default="4,8,16")
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots"])
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--overlap", default="on",
                    choices=["on", "off", "both"],
                    help="communication-overlap pass A-B mode")
    ap.add_argument("--parity", default="bitwise",
                    choices=["bitwise", "relaxed", "both"],
                    help="parity tier rungs (parallel/lowp)")
    ap.add_argument("--sync-schedule", default="full",
                    help="relaxed-tier TP activation-sync schedule "
                         "(parallel.lowp.sync.schedule: full | none | "
                         "periodic:<k> | layers:<spec>) — priced on "
                         "the relaxed rung and recorded in its policy "
                         "dict + comm ledger")
    ap.add_argument("--sync-mode", default="skip",
                    choices=["skip", "stale"],
                    help="what a scheduled-off layer does "
                         "(parallel.lowp.sync.mode)")
    ap.add_argument("--guard-steps", type=int, default=0,
                    help="also run the relaxed loss-curve A-B guard "
                         "over this many steps (0 = skip)")
    ap.add_argument("--ckpt", default="none",
                    choices=["none", "sync", "async", "both"],
                    help="include a checkpoint blocking-time breakdown")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON object instead of per-line text")
    args = ap.parse_args()
    remat = {"none": False, "full": True, "dots": "dots"}[args.remat]

    from hadoop_tpu.util.jaxcache import configure_compile_cache
    configure_compile_cache()
    cfg = get_config(args.preset, max_seq=args.seq)
    plan = MeshPlan(dp=args.dp, tp=args.tp, pp=args.pp,
                    megatron_sp=args.tp > 1)
    mesh = make_mesh(plan)
    params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan, mesh)
    ds = make_data_sharding(mesh)
    overlaps = {"on": [("overlap-on", DEFAULT_OVERLAP)],
                "off": [("overlap-off", OVERLAP_OFF)],
                "both": [("overlap-on", DEFAULT_OVERLAP),
                         ("overlap-off", OVERLAP_OFF)]}[args.overlap]

    from hadoop_tpu.models.decoder import forward_hidden
    from hadoop_tpu.parallel.train import _loss_from_h
    report: dict = {"preset": args.preset, "seq": args.seq,
                    "plan": {"dp": args.dp, "tp": args.tp, "pp": args.pp},
                    "remat": args.remat, "params": count_params(params),
                    "parity_mode": args.parity,
                    "batches": []}
    for batch in [int(x) for x in args.batches.split(",")]:
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (batch, args.seq), 0,
                               cfg.vocab_size, dtype=jnp.int32), ds)
        targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)

        ctx = plan.ctx(cfg)

        @jax.jit
        def fwd_only(params, tokens, targets):
            h = forward_hidden(params, tokens, cfg, ctx, remat=remat)
            return _loss_from_h(params, h, targets, cfg, ctx)

        @jax.jit
        def fwd_bwd(params, tokens, targets):
            def f(p):
                h = forward_hidden(p, tokens, cfg, ctx, remat=remat)
                return _loss_from_h(p, h, targets, cfg, ctx)
            return jax.value_and_grad(f)(params)

        from hadoop_tpu.parallel.lowp import (BITWISE_PARITY,
                                              ParityConfig)
        from hadoop_tpu.parallel.lowp.quant import capture_comm
        relaxed_par = ParityConfig(
            tier="relaxed", relaxed_sync=args.sync_schedule,
            relaxed_sync_mode=args.sync_mode)
        parities = {"bitwise": [("", BITWISE_PARITY)],
                    "relaxed": [("parity-relaxed_", relaxed_par)],
                    "both": [("", BITWISE_PARITY),
                             ("parity-relaxed_", relaxed_par)]}[
            args.parity]
        row: dict = {"batch": batch}
        # single-trace components are only meaningful single-device (no
        # collectives outside shard_map); skip them on multichip plans
        if plan.n_devices == 1:
            row["fwd_ms"] = round(
                timeit(fwd_only, params, tokens, targets) * 1e3, 1)
            t_fb = timeit(fwd_bwd, params, tokens, targets)
            row["bwd_ms"] = round(t_fb * 1e3 - row["fwd_ms"], 1)
        for plabel, par in parities:
            for olabel, ov in overlaps:
                if par.relaxed and not ov.enabled:
                    # relaxed rides the overlap pass's collectives;
                    # make_train_step refuses the combination
                    continue
                label = plabel + olabel
                try:
                    with capture_comm() as ledger:
                        step = make_train_step(cfg, plan, mesh,
                                               remat=remat,
                                               donate=False, overlap=ov,
                                               parity=par)
                        t_full = timeit(step, params, opt, tokens,
                                        targets)
                except Exception as e:  # noqa: BLE001 — a step that
                    # cannot run on this backend (e.g. no vma tracking)
                    # is a data point; the fwd/bwd and ckpt numbers
                    # must still land
                    row[label + "_error"] = f"{type(e).__name__}"
                    continue
                row[label + "_ms"] = round(t_full * 1e3, 1)
                row[label + "_tok_s"] = round(batch * args.seq / t_full)
                if par.relaxed and ledger.sites:
                    row[label + "_comm"] = ledger.report()
                    # the policy that produced this row, next to its
                    # ledger — bench rows stay self-describing when
                    # tiers multiply (codec/group/consumer flags here,
                    # the serving weight plane in serve_bench's JSON)
                    row[label + "_policy"] = dataclasses.asdict(par)
        if "fwd_ms" in row and "overlap-on_ms" in row:
            # optimizer + (unoverlapped) comm residue: what the full
            # step spends beyond fwd+bwd compute
            row["opt_comm_ms"] = round(
                row["overlap-on_ms"] - row["fwd_ms"] - row["bwd_ms"], 1)
        if "overlap-on_ms" in row and "overlap-off_ms" in row:
            row["overlap_gain_ms"] = round(
                row["overlap-off_ms"] - row["overlap-on_ms"], 1)
        report["batches"].append(row)
        if not args.json:
            print(" ".join(f"{k}={v}" for k, v in row.items()))

    if args.guard_steps > 0:
        # loss-curve A-B acceptance (parallel/lowp/guard.py): the
        # relaxed trajectory must stay within the bounded divergence
        # of its bitwise twin. Recorded verbatim in the JSON.
        from hadoop_tpu.parallel.lowp import ParityConfig
        from hadoop_tpu.parallel.lowp.guard import run_loss_ab
        try:
            report["parity_guard"] = run_loss_ab(
                plan, preset=args.preset, steps=args.guard_steps,
                seq=min(args.seq, 128),
                parity=ParityConfig(tier="relaxed",
                                    relaxed_sync=args.sync_schedule,
                                    relaxed_sync_mode=args.sync_mode))
        except Exception as e:  # noqa: BLE001 — a backend that cannot
            # run the step records the gap instead of dying
            report["parity_guard"] = {"error": f"{type(e).__name__}"}
        if not args.json:
            pg = report["parity_guard"]
            print("parity_guard " + " ".join(
                f"{k}={pg[k]}" for k in ("accepted", "max_rel_div",
                                         "final_rel_div", "reason")
                if k in pg))

    if args.ckpt != "none":
        report["ckpt"] = ckpt_breakdown(params, opt, args.ckpt)
        if not args.json:
            print("ckpt " + " ".join(
                f"{k}={v}" for k, v in report["ckpt"].items()))
    if args.json:
        print(json.dumps(report))


if __name__ == "__main__":
    main()
