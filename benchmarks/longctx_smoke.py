"""Long-context serving smoke: a prompt 8x one chip's KV budget, end
to end through the real door.

CPU functional test: JAX_PLATFORMS=cpu in parent and children; no chip number.

Runs in a SUBPROCESS with an 8-virtual-device CPU mesh (the
minicluster philosophy: real protocols, simulated fleet) so the parent
bench process keeps its own device topology. The contract, all
recorded in the JSON and collected into ``failed``:

- a prompt >= 8x the engine's usable KV pool (at the fixed
  ``serving.kv.hbm.bytes`` budget) POSTs through ``/v1/generate`` and
  the decoded tokens EXACTLY match a single-chip ``decoder.forward``
  greedy reference (raw KV codec arm);
- the CP prefill guards accept: exact at a small shape for ring AND
  ulysses (``run_weight_ab``-style), relaxed logits guard at the
  monster shape;
- the KV chain streamed into the tiers and paged back: host-ring hits
  AND DFS hits AND DFS persists all > 0 (the host ring is sized
  smaller than the chain on purpose), ``chain_ingested`` equals the
  full-block count;
- compile-once: the plane's prefill executable traced once, every
  paged-decode jit traced once, and a short prompt through the same
  door still rides the fused step at exactly one trace per shape;
- TTFT per CP width (1/2/4/8 chips) recorded — on the shared-core CPU
  sim the wall-clock scaling is NOT asserted (all "chips" are one
  host), the numbers are the trajectory for real-hardware runs.

An int8-codec arm re-runs the monster prompt with the KV chain stored
int8 in the host ring (relaxed guard accepted; token match vs the raw
arm recorded, not asserted — codec noise may legitimately flip a
near-tie greedy pick).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _reference_greedy(params, cfg, prompt, n):
    import jax.numpy as jnp

    from hadoop_tpu.models.decoder import forward
    ctx = list(prompt)
    out = []
    for _ in range(n):
        lg = forward(params, jnp.asarray(ctx, jnp.int32)[None, :],
                     cfg)[0, -1]
        tok = int(jnp.argmax(lg))
        out.append(tok)
        ctx.append(tok)
    return out


def _post(port, payload, timeout=600.0):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate",
                     body=json.dumps(payload).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, json.loads(body)
    finally:
        conn.close()


def child(quick: bool = False) -> dict:
    import tempfile

    import jax
    import numpy as np

    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.models.config import get_config
    from hadoop_tpu.models.decoder import init_params
    from hadoop_tpu.parallel.lowp.guard import ParityGuardError
    from hadoop_tpu.serving.engine import DecodeEngine
    from hadoop_tpu.serving.longctx import (ContextParallelPrefiller,
                                            LongContextPlane,
                                            run_prefill_ab)
    from hadoop_tpu.serving.longctx.decode import trace_counts
    from hadoop_tpu.serving.metrics import ServingMetrics
    from hadoop_tpu.serving.server import ServingServer
    from hadoop_tpu.serving.weightplane import describe_tree
    from hadoop_tpu.testing.minicluster import MiniDFSCluster, fast_conf

    n_dev = len(jax.devices())
    assert n_dev >= 8, f"need the 8-virtual-device mesh, got {n_dev}"
    bs = 8
    prompt_len = 1024 if quick else 2048
    pool_blocks = prompt_len // bs // 8   # prompt = 8x usable pool
    max_new = 6
    cfg = get_config("tiny", max_seq=prompt_len + 256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=prompt_len).tolist()
    block_nbytes = (2 * cfg.n_layers * bs * cfg.n_kv_heads *
                    cfg.head_dim * np.dtype(cfg.dtype).itemsize)
    weight_bytes = describe_tree(params)["weight_bytes"]
    hbm_bytes = weight_bytes + (pool_blocks + 1) * block_nbytes
    # host ring holds only a quarter of the chain: decode MUST hit the
    # DFS tier for the head of the context
    host_bytes = (prompt_len // bs // 4) * block_nbytes
    out: dict = {"prompt_tokens": prompt_len, "block_size": bs,
                 "kv_pool_blocks": pool_blocks,
                 "kv_pool_tokens": pool_blocks * bs,
                 "prompt_over_pool": prompt_len / (pool_blocks * bs),
                 "hbm_bytes": hbm_bytes, "host_bytes": host_bytes}
    failed = []

    dconf = fast_conf()
    dconf.set("dfs.replication", "1")
    ref = _reference_greedy(params, cfg, prompt, max_new)
    out["reference_tokens"] = ref
    with tempfile.TemporaryDirectory() as tmp, \
            MiniDFSCluster(num_datanodes=1, conf=dconf,
                           base_dir=os.path.join(tmp, "dfs")) as c:
        c.wait_active()
        # a copy: under a budget the engine frees the stacks it
        # re-places, and the plane below reads them by name
        engine = DecodeEngine(
            jax.tree_util.tree_map(jax.numpy.copy, params), cfg,
            block_size=bs, max_context=64,
            prefill_chunk=8, hbm_bytes=hbm_bytes,
            kv_host_bytes=host_bytes, kv_store_fs=c.get_filesystem(),
            kv_store_dir="/kvcache", metrics=ServingMetrics())
        plane = LongContextPlane(
            params, cfg, engine.kvstore, block_size=bs,
            min_tokens=512, max_tokens=prompt_len, sp=8,
            window_blocks=4, tail_tokens=64, metrics=engine.metrics)
        engine.attach_longctx(plane)
        engine.start()
        server = ServingServer(engine, Configuration())
        server.start()
        try:
            t0 = time.monotonic()
            status, resp = _post(server.port,
                                 {"tokens": prompt,
                                  "max_new_tokens": max_new,
                                  "timeout": 590})
            door_wall = time.monotonic() - t0
            out["door_status"] = status
            out["door_tokens"] = resp.get("tokens")
            out["door_wall_seconds"] = round(door_wall, 3)
            if status != 200:
                failed.append(f"door returned {status}: {resp}")
            elif resp.get("tokens") != ref:
                failed.append(
                    f"door tokens {resp.get('tokens')} != single-chip "
                    f"reference {ref}")
            # a short prompt beside the monster: the fused step still
            # compiles exactly once per shape, untouched by the plane
            status2, resp2 = _post(server.port,
                                   {"tokens": prompt[:24],
                                    "max_new_tokens": 3,
                                    "timeout": 120})
            if status2 != 200:
                failed.append(f"short-prompt door returned {status2}")
            kv = engine.kvstore.stats()
            out["kv"] = kv
            if kv["hits_host"] <= 0:
                failed.append("no host-tier hits paging the chain")
            if kv["hits_dfs"] <= 0:
                failed.append("no DFS-tier hits paging the chain "
                              "(ring sized to force them)")
            if kv["dfs_persists"] <= 0:
                failed.append("no DFS persists of the streamed chain")
            if kv["chain_ingested"] != prompt_len // bs:
                failed.append(
                    f"chain_ingested {kv['chain_ingested']} != "
                    f"{prompt_len // bs}")
            st = plane.stats()
            out["longctx"] = st
            if st["prefill_compiles"] != 1:
                failed.append(f"CP prefill traced "
                              f"{st['prefill_compiles']}x (pinned: 1)")
            bad = {k: v for k, v in trace_counts().items() if v != 1}
            if bad:
                failed.append(f"paged-decode retracing: {bad}")
            if engine.decode_compiles != 1 or \
                    engine.prefill_compiles != 1:
                failed.append(
                    f"fused step shapes traced decode="
                    f"{engine.decode_compiles} prefill="
                    f"{engine.prefill_compiles} (pinned: 1 each)")
        finally:
            server.stop()

    # ---- guards: exact at small shape (ring + ulysses), relaxed at
    # the monster shape
    small = rng.integers(0, cfg.vocab_size, size=150).tolist()
    for mode, sp in (("ring", 4), ("ulysses", 2)):
        try:
            pre = ContextParallelPrefiller(
                params, cfg, block_size=bs, pad_tokens=len(small) + 10,
                sp=sp, sp_mode=mode)
            out[f"guard_exact_{mode}"] = run_prefill_ab(
                params, cfg, small, pre, mode="exact")
        except ParityGuardError as e:
            failed.append(f"exact {mode} guard rejected: {e}")
    # ---- TTFT vs chips at the monster shape (+ the big-shape relaxed
    # guard off the 8-chip arm)
    ttft = {}
    for sp in (1, 2, 4, 8):
        pre = ContextParallelPrefiller(params, cfg, block_size=bs,
                                       pad_tokens=prompt_len, sp=sp)
        pre.cp_prefill(prompt)          # warm (the one trace)
        secs = min(pre.cp_prefill(prompt).seconds for _ in range(2))
        ttft[str(sp)] = round(secs, 4)
        if sp == 8:
            try:
                out["guard_relaxed_big"] = run_prefill_ab(
                    params, cfg, prompt, pre, mode="relaxed",
                    rel_tol=0.05)
            except ParityGuardError as e:
                failed.append(f"relaxed big-shape guard rejected: {e}")
    out["ttft_by_chips_seconds"] = ttft
    out["ttft_note"] = ("CPU-sim chips share one host's cores: "
                        "wall-clock scaling is recorded, not asserted")

    # ---- decode throughput rung: the pipelined/fused path vs the
    # legacy per-(layer, window) loop, at 1x and 8x the engine's KV
    # pool. The dispatch and transfer budgets are asserted — they are
    # deterministic counters; tokens/s is recorded as data (CPU-sim
    # walls, the TTFT convention).
    from hadoop_tpu.serving.engine import SamplingParams
    from hadoop_tpu.serving.longctx.decode import WorkingSetDecoder

    engine_d = DecodeEngine(
        params, cfg, block_size=bs, max_context=64, prefill_chunk=8,
        kv_host_bytes=(2 * prompt_len // bs + 8) * block_nbytes,
        metrics=ServingMetrics())
    short_ctx = pool_blocks * bs          # 1x the engine's usable pool
    decode = {}
    for label, toks in (("1x", prompt[:short_ctx]), ("8x", prompt)):
        res = pre.cp_prefill(toks)        # the warmed sp=8 executable
        engine_d.kvstore.ingest_chain(toks, res.blocks)
        first = int(np.argmax(res.last_logits))
        n_win = -(-len(toks) // (4 * bs))
        arms = {}
        for path, pipeline in (("pipelined", True), ("legacy", False)):
            dec = WorkingSetDecoder(
                params, cfg, engine_d.kvstore, block_size=bs,
                window_blocks=4, tail_tokens=64, pipeline=pipeline)
            got = []
            dec.paged_decode(toks, first,
                             SamplingParams(max_new_tokens=2),
                             deliver=got.append, seed=1)    # warm
            t0 = time.monotonic()
            emitted = dec.paged_decode(toks, first,
                                       SamplingParams(max_new_tokens=9),
                                       deliver=got.append, seed=1)
            wall = time.monotonic() - t0
            arms[path] = {
                "tokens_per_sec": round(emitted / max(wall, 1e-9), 2),
                "dispatches_per_token":
                    round(dec.dispatches_per_token, 2),
                "window_fetches": dec.window_fetches,
                "hbm_window_bytes": dec.hbm_window_bytes,
            }
            if pipeline and dec.dispatches_per_token > 2 * n_win + 1:
                failed.append(
                    f"{label} fused dispatches/token "
                    f"{dec.dispatches_per_token:.1f} over the 2 per "
                    f"(token, window) + head budget {2 * n_win + 1}")
        if arms["pipelined"]["window_fetches"] >= \
                arms["legacy"]["window_fetches"]:
            failed.append(
                f"{label}: pipelined slab transfers not below the "
                f"legacy per-(layer, window) slices")
        if arms["pipelined"]["dispatches_per_token"] >= \
                arms["legacy"]["dispatches_per_token"]:
            failed.append(f"{label}: fusion did not reduce dispatches "
                          f"per token")
        decode[label] = arms
    engine_d.stop()
    out["decode"] = decode
    f8, f1 = decode["8x"]["pipelined"], decode["1x"]["pipelined"]
    out["decode_tokens_per_sec"] = f8["tokens_per_sec"]
    out["decode_dispatches_per_token"] = f8["dispatches_per_token"]
    out["decode_hbm_window_bytes"] = f8["hbm_window_bytes"]
    out["decode_slowdown_8x_vs_1x"] = round(
        f1["tokens_per_sec"] / max(f8["tokens_per_sec"], 1e-9), 2)
    out["decode_note"] = ("CPU-sim walls: tokens/s recorded, not "
                          "asserted; the dispatch/transfer budgets are "
                          "asserted on their deterministic counters")

    # ---- int8 codec arm: chain stored int8 in the host ring
    engine8 = DecodeEngine(
        jax.tree_util.tree_map(jax.numpy.copy, params), cfg,
        block_size=bs, max_context=64, prefill_chunk=8,
        hbm_bytes=hbm_bytes,
        kv_host_bytes=(prompt_len // bs + 8) * block_nbytes,
        kv_codec="int8", metrics=ServingMetrics())
    plane8 = LongContextPlane(
        params, cfg, engine8.kvstore, block_size=bs, min_tokens=512,
        max_tokens=prompt_len, sp=8, window_blocks=4, tail_tokens=64,
        metrics=engine8.metrics)
    engine8.attach_longctx(plane8)
    req = engine8.submit(prompt, SamplingParams(max_new_tokens=max_new))
    try:
        toks8 = req.wait(300)
        out["int8_tokens"] = toks8
        out["int8_matches_raw"] = toks8 == ref   # recorded, not asserted
    except (RuntimeError, TimeoutError) as e:
        failed.append(f"int8-codec arm failed to decode: {e}")
    engine8.stop()

    out["failed"] = failed
    return out


def run(quick: bool = False) -> dict:
    """Spawn the smoke in its own 8-virtual-device process and return
    its JSON (the run_all entry — recorded, not raised)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable, "-m", "benchmarks.longctx_smoke", "--child"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(
        cmd, cwd=os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), env=env, capture_output=True, text=True,
        timeout=1800)
    if proc.returncode != 0:
        return {"error": f"child exited {proc.returncode}",
                "stderr": proc.stderr[-2000:]}
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    return {"error": "no JSON in child stdout",
            "stdout": proc.stdout[-2000:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="run the smoke in THIS process (expects the "
                         "8-virtual-device env)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        os.environ["JAX_PLATFORMS"] = "cpu"
        result = child(quick=args.quick)
    else:
        result = run(quick=args.quick)
    print(json.dumps(result))
    return 1 if (result.get("failed") or result.get("error")) else 0


if __name__ == "__main__":
    sys.exit(main())
