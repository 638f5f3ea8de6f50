#!/usr/bin/env python3
"""Does the system still start on the chip?  train -> checkpoint -> serve.

Drives the main path once, through the entry points a user would call, at
the full width and depth of ``flagship-1b`` (d 2048, 16x128 heads / 8 KV,
d_ff 5632, 18 layers, vocab 32768, bf16):

  K  kernels   every public entry of ops/flash.py compiled by Mosaic
               (interpret=False) and checked against the jnp reference
  T  train     parallel.trainer.Trainer over a token file on the miniDFS,
               then the params checkpointed to the DFS
  S  serve     ``bin/hadoop-tpu serve`` on that checkpoint, driven over HTTP
  M  (--chips 4 only) leg T under MeshPlan(dp=2, tp=2, megatron_sp=True)

    python chip_smoke.py            # one chip: K, T, S
    python chip_smoke.py --chips 4  # one four-chip host: M

A chip belongs to one process at a time, so this parent never touches JAX:
it owns a MiniDFSCluster + RegistryServer for the whole run and starts each
leg as ONE child process at a time. It exits non-zero if any leg fails, if a
child's ``jax.devices()[0].platform`` is not "tpu", or if JAX_PLATFORMS pins
JAX to the CPU. On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--dry-cpu`` is the only way the device check is waived: the same legs at
the ``tiny`` preset on the CPU backend (Pallas in interpret mode), for
debugging this script where there is no chip. It proves nothing about the
chip and says so.

No number printed here is a benchmark; leg T's tokens/s is shown with its
device line so a gross slowdown is visible by eye.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

RUN_BUDGET_S = 1150.0          # the whole script, compilation included
AUTH_SECRET = "chip-smoke-secret"
SERVICE = "chip-smoke"

# bf16 kernel vs f32 reference (same bf16 inputs, matmuls at "highest"):
# bf16 keeps 8 mantissa bits (2^-9 ~ 2e-3 per rounding) and the kernel
# rounds P / dS to bf16 before the second matmul and the outputs to bf16.
# 3e-2 of the tensor's scale is the repo's own bf16-vs-f32 bound
# (tests/test_flash.py): ten times the rounding noise, far below what a
# wrong mask, block index or scale produces (O(1)).
KERNEL_TOL = 3e-2
# the log-sum-exp stays f32 end to end in kernel and reference
LSE_TOL = 1e-2


class LegFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


# ===================================================================== parent

def cache_entries() -> int:
    from hadoop_tpu.util.jaxcache import compile_cache_dir
    try:
        return len(os.listdir(compile_cache_dir()))
    except FileNotFoundError:
        return 0


class Children:
    """Every process this script starts, so all of them get stopped."""

    def __init__(self):
        self.procs = []

    def spawn(self, cmd, env, **kw) -> subprocess.Popen:
        proc = subprocess.Popen(cmd, env=env, start_new_session=True, **kw)
        self.procs.append(proc)
        return proc

    @staticmethod
    def kill(proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    def kill_all(self) -> None:
        for proc in self.procs:
            self.kill(proc)


def child_env(dry: bool, chips: int) -> dict:
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    if dry:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{chips}").strip()
    return env


def run_leg(children: Children, leg: str, spec: dict, env: dict,
            timeout: float) -> dict:
    """One leg = one child process; returns its RESULT object."""
    proc = children.spawn(
        [sys.executable, os.path.abspath(__file__), "--child", leg,
         "--spec", json.dumps(spec)],
        env, stdout=subprocess.PIPE, text=True)
    result = {}

    def pump():
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith("RESULT "):
                result.update(json.loads(line[len("RESULT "):]))
            else:
                print(f"[{leg}] {line}", flush=True)

    reader = threading.Thread(target=pump, name=f"pump-{leg}")
    reader.start()
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        children.kill(proc)
        reader.join()
        raise LegFailed(f"leg {leg}: no result within {timeout:.0f}s")
    reader.join()
    check(rc == 0 and bool(result),
          f"leg {leg}: child exited {rc}"
          f"{'' if result else ' without a result'}")
    return result


def http_json(port: int, method: str, path: str, payload=None,
              timeout: float = 300.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        body = json.dumps(payload).encode() if payload is not None else None
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    lines = [json.loads(ln) for ln in raw.decode().splitlines() if ln]
    return resp.status, lines


def leg_serve(children: Children, cluster, reg_srv, spec: dict, env: dict,
              logdir: str, deadline: float) -> dict:
    """Leg S: the real CLI door as a child, driven over HTTP."""
    from hadoop_tpu.util.misc import free_port
    dry = spec["dry"]
    port = free_port()
    log_path = os.path.join(logdir, "serve.log")
    cmd = [sys.executable, os.path.join(HERE, "bin", "hadoop-tpu"), "serve",
           "-D", f"serving.http.auth.secret={AUTH_SECRET}",
           "--name", SERVICE,
           "--checkpoint", f"{cluster.default_fs}{spec['ckpt']}",
           "--preset", spec["preset"],
           "--registry", f"127.0.0.1:{reg_srv.port}",
           "--port", str(port)]
    t_spawn = time.monotonic()
    with open(log_path, "w") as logf:
        proc = children.spawn(cmd, env, stdout=logf,
                              stderr=subprocess.STDOUT)

    def fail(what: str):
        with open(log_path) as f:
            tail = f.read()[-3000:]
        raise LegFailed(f"leg S: {what}\n--- serve.log tail ---\n{tail}")

    health = None
    while health is None:
        if proc.poll() is not None:
            fail(f"replica exited {proc.returncode} before serving")
        if time.monotonic() > deadline:
            fail("replica not healthy before the run's deadline")
        try:
            status, body = http_json(port, "GET", "/v1/health", timeout=5.0)
            if status == 200:
                health = body[0]
        except (OSError, http.client.HTTPException):
            time.sleep(0.5)
    ready_s = time.monotonic() - t_spawn
    print(f"[S] replica healthy after {ready_s:.1f}s on :{port}", flush=True)

    device = health["hbm"]["device"]
    if dry:
        print(f"[S] DRY CPU: hbm.device={device} (device check waived)")
    else:
        if not device or device.get("platform") != "tpu":
            fail(f"hbm.device.platform is not tpu: {device}")
        if not device.get("bytes_in_use"):
            fail(f"hbm.device.bytes_in_use is zero: {device}")
    records = reg_srv.list("/")
    if len(records) != 1 or \
            records[0].endpoints.get("http", "").rpartition(":")[2] != \
            str(port):
        fail(f"expected one registry record for :{port}, got "
             f"{[r.path for r in records]}")

    vocab, chunk = spec["vocab"], health["prefix_cache"]["prefill_chunk"]
    max_new = spec["max_new"]
    door = "/v1/generate?user.name=smoke"

    def prompt(n: int, salt: int):
        return [(7 * i + 13 * salt + 1) % vocab for i in range(n)]

    def generate(tokens, **extra):
        status, body = http_json(
            port, "POST", door,
            dict(tokens=tokens, max_new_tokens=max_new, **extra))
        if status != 200:
            fail(f"generate answered {status}: {body}")
        return body

    status, _ = http_json(port, "POST", "/v1/generate",
                          {"tokens": [1, 2, 3], "max_new_tokens": 1})
    if status != 401:
        fail(f"an unauthenticated request got {status}, not 401")

    short, long_ = prompt(8, 1), prompt(3 * chunk + chunk // 2, 2)
    t0 = time.monotonic()
    first = generate(short)[0]["tokens"]
    first_s = time.monotonic() - t0
    if len(first) != max_new:
        fail(f"short prompt: {len(first)} tokens back, wanted {max_new}")
    long_out = generate(long_)[0]["tokens"]
    if len(long_out) != max_new:
        fail(f"long prompt ({len(long_)} tokens, chunk {chunk}): "
             f"{len(long_out)} tokens back")

    both = [None, None]

    def one(i):
        both[i] = generate(prompt(11 + 5 * i, 3 + i))[0]["tokens"]

    threads = [threading.Thread(target=one, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if not all(out and len(out) == max_new for out in both):
        fail(f"concurrent requests: {both}")

    lines = generate(prompt(9, 5), stream=True)
    streamed = [ln["token"] for ln in lines if "token" in ln]
    if not lines[-1].get("done") or lines[-1].get("tokens") != streamed \
            or len(streamed) != max_new:
        fail(f"stream: {lines}")

    again, long_again = generate(short)[0]["tokens"], \
        generate(long_)[0]["tokens"]
    if again != first or long_again != long_out:
        fail(f"greedy output changed on repeat: {first} -> {again}; "
             f"{long_out} -> {long_again}")

    _, body = http_json(port, "GET", "/v1/health")
    compiles = body[0]["compiles"]
    if not (compiles["decode"] == 1 and compiles["prefill"] == 1):
        fail(f"step shapes must each compile exactly once: {compiles}")

    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=max(5.0, min(120.0,
                                            deadline - time.monotonic())))
    except subprocess.TimeoutExpired:
        fail("replica did not exit after SIGTERM")
    if rc != 0:
        fail(f"replica exited {rc} after SIGTERM")
    left = [r.path for r in reg_srv.list("/")]
    if left:
        fail(f"registry record survived the drain: {left}")
    return {"setup_s": round(ready_s + first_s, 1),
            "ready_s": round(ready_s, 1),
            "first_request_s": round(first_s, 2),
            "requests": 8, "compiles": compiles,
            "hbm_device": device, "drain_rc": rc}


def parent_main(args) -> int:
    dry, chips = args.dry_cpu, args.chips
    pinned = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if pinned and "tpu" not in pinned.split(",") and not dry:
        print(f"chip_smoke: JAX_PLATFORMS={pinned!r} pins JAX to platform "
              f"{pinned!r}; this script passes only on a TPU (use "
              f"--dry-cpu to debug the script itself off the chip)",
              file=sys.stderr)
        return 2
    if dry:
        print("=" * 72 + "\nDRY RUN ON THE CPU BACKEND at preset 'tiny': "
              "debugs this script only.\nNOT a chip result; every device "
              "check below is waived.\n" + "=" * 72, flush=True)
    deadline = time.monotonic() + RUN_BUDGET_S

    import hadoop_tpu.native as native
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.registry import RegistryServer
    from hadoop_tpu.testing.minicluster import MiniDFSCluster, fast_conf
    check("jax" not in sys.modules, "the parent imported jax")
    print(f"native library: "
          f"{'loaded' if native.available() else 'NOT LOADED'} "
          f"({native._LIB_PATH})", flush=True)
    # without it CRC and packet framing run in pure Python and the ~2 GB
    # checkpoint crawls for minutes instead of failing here
    check(native.available(), "hadoop_tpu/native did not build or load "
          "(needs make + g++); refusing to crawl through pure-Python CRC")

    preset = "tiny" if dry else "flagship-1b"
    import numpy as np
    seq, vocab = (128, 256) if dry else (2048, 32768)
    batch = 4 if chips == 4 else 2
    # two batches of seeded tokens, cycled by the dataset: a loss that
    # does not fall on a stream this short means the step is broken
    tokens = np.random.default_rng(0).integers(
        0, vocab, size=2 * batch * (seq + 1)).astype(np.uint16)

    children = Children()
    base = tempfile.mkdtemp(prefix="htpu-chip-smoke-")
    conf = fast_conf()
    conf.set("dfs.replication", "1")
    conf.set("dfs.blocksize", "64m")
    # the one DataNode shares this interpreter with the 2 GB checkpoint
    # stream: fast_conf's 1.5 s death sentence (test-speed failure
    # detection) would fire whenever the heartbeat thread is starved
    conf.set("dfs.heartbeat.interval", "1s")
    conf.set("dfs.namenode.heartbeat.recheck-interval", "30s")
    cluster = MiniDFSCluster(num_datanodes=1, conf=conf,
                             base_dir=os.path.join(base, "dfs"))
    reg_conf = Configuration(load_defaults=False)
    reg_srv = RegistryServer(reg_conf)
    env = child_env(dry, chips)
    results = {}
    try:
        cluster.start()
        reg_srv.init(reg_conf)
        reg_srv.start()
        cluster.get_filesystem().write_all("/data/tokens.bin",
                                           tokens.tobytes())
        common = {"dry": dry, "preset": preset, "fs": cluster.default_fs,
                  "data": "/data/tokens.bin", "ckpt": f"/models/{preset}",
                  "vocab": vocab, "max_new": 8}
        plan = {"dp": 2, "tp": 2, "megatron_sp": True} if chips == 4 else {}
        legs = ["M"] if chips == 4 else ["K", "T", "S"]
        device = None
        for leg in legs:
            before, t0 = cache_entries(), time.monotonic()
            left = deadline - time.monotonic()
            if leg == "S":
                res = leg_serve(children, cluster, reg_srv, common, env,
                                base, deadline)
            else:
                res = run_leg(children, leg,
                              dict(common, batch=batch, plan=plan,
                                   chips=chips), env, left)
                check(device in (None, res["device"]),
                      f"leg {leg} ran on {res['device']}, not {device}")
                device = res["device"]
            res["wall_s"] = round(time.monotonic() - t0, 1)
            res["cache_entries"] = [before, cache_entries()]
            results[leg] = res
            print(f"leg {leg} PASSED " + json.dumps(
                {k: v for k, v in res.items() if k != "device"}),
                flush=True)
    finally:
        children.kill_all()
        reg_srv.stop()
        cluster.shutdown()
        import shutil
        shutil.rmtree(base, ignore_errors=True)

    from hadoop_tpu.util.jaxcache import compile_cache_dir
    print(f"compile cache {compile_cache_dir()}; set-up per leg (process start to "
          f"first step / first answer), cache entries before -> after: " +
          ", ".join(f"{leg} {r['setup_s']}s [{r['cache_entries'][0]} -> "
                    f"{r['cache_entries'][1]}]"
                    for leg, r in results.items()), flush=True)
    check(dry or device["platform"] == "tpu",
          f"the legs ran on platform {device['platform']!r}")
    out = {"ok": True, "device": {k: device[k]
                                  for k in ("platform", "kind", "count")}}
    if dry:
        out["dry_cpu"] = True
    print(json.dumps(out), flush=True)
    return 0


# =================================================================== children

def child_device(spec: dict) -> dict:
    """Initialize JAX in this child; refuse anything but a TPU."""
    import jax
    from hadoop_tpu.util.jaxcache import configure_compile_cache
    configure_compile_cache()
    dev = jax.devices()[0]
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "libtpu": libtpu}
    print("device platform={platform} device_kind={kind!r} count={count} "
          "jax={jax} libtpu={libtpu}".format(**info), flush=True)
    if spec["dry"]:
        print("DRY CPU: device check waived", flush=True)
    else:
        check(dev.platform == "tpu",
              f"jax.devices()[0].platform is {dev.platform!r}, not 'tpu'")
    check(info["count"] >= spec["chips"],
          f"--chips {spec['chips']} but jax sees {info['count']} devices")
    return info


class CompileCounter:
    """Backend compiles seen by this process (jax.monitoring)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def child_kernels(spec: dict) -> dict:
    """Leg K: every public entry of ops/flash.py, Mosaic-compiled."""
    t_start = time.monotonic()
    info = child_device(spec)
    import jax
    import jax.numpy as jnp
    from hadoop_tpu.ops.attention import (_repeat_kv, causal_attention,
                                          chunk_attention)
    from hadoop_tpu.ops.flash import (flash_attention,
                                      flash_attention_partial)
    interp = spec["dry"]
    # (label, seq, q heads, kv heads, head_dim): flagship-1b's attention
    # (GQA 16/8 x 128 at seq 2048) and flagship-420m's head_dim 64
    cases = [("hd128", 256, 4, 2, 128), ("hd64", 256, 4, 2, 64)] if interp \
        else [("hd128", 2048, 16, 8, 128), ("hd64", 2048, 16, 8, 64)]
    f32 = jnp.float32

    def err(got, ref):
        got, ref = jnp.asarray(got, f32), jnp.asarray(ref, f32)
        check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
        check(bool(jnp.all(jnp.isfinite(got))), "non-finite kernel output")
        return float(jnp.max(jnp.abs(got - ref)) /
                     jnp.maximum(1.0, jnp.max(jnp.abs(ref))))

    checks = {}
    for label, s, hq, hkv, d in cases:
        ks = jax.random.split(jax.random.PRNGKey(d), 4)
        q = jax.random.normal(ks[0], (1, s, hq, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, s, hkv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, s, hkv, d), jnp.bfloat16)
        w = jax.random.normal(ks[3], (1, s, hq, d), f32)
        scale = 1.0 / d ** 0.5
        n_rep = hq // hkv

        def ref_loss(q, k, v):
            return jnp.sum(causal_attention(q, k, v, impl="ref") * w)

        def got_loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, interpret=interp)
                           .astype(f32) * w)

        def ref_partial(q, k, v, causal):
            sq, skv = q.shape[1], k.shape[1]
            q_pos = jnp.arange(sq) if causal else jnp.full((sq,), skv)
            return chunk_attention(q, _repeat_kv(k, n_rep),
                                   _repeat_kv(v, n_rep), scale, q_pos,
                                   jnp.arange(skv))

        with jax.default_matmul_precision("highest"):
            q32, k32, v32 = (x.astype(f32) for x in (q, k, v))
            ref_o = jax.jit(lambda q, k, v: causal_attention(
                q, k, v, impl="ref"))(q32, k32, v32)
            ref_g = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(
                q32, k32, v32)
            ref_pc = jax.jit(lambda q, k, v: ref_partial(q, k, v, True))(
                q32, k32, v32)
            ref_pn = jax.jit(lambda q, k, v: ref_partial(q, k, v, False))(
                q32[:, :s // 2], k32, v32)

        got_o = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, interpret=interp))(q, k, v)
        got_g = jax.jit(jax.grad(got_loss, argnums=(0, 1, 2)))(q, k, v)
        got_pc = jax.jit(lambda q, k, v: flash_attention_partial(
            q, k, v, scale, True, interp))(q, k, v)
        # the ring's off-diagonal chunk: every key visible, Sq != Skv
        got_pn = jax.jit(lambda q, k, v: flash_attention_partial(
            q, k, v, scale, False, interp))(q[:, :s // 2], k, v)

        found = {"fwd": (err(got_o, ref_o), KERNEL_TOL)}
        for name, g, r in zip(("dq", "dk", "dv"), got_g, ref_g):
            found[name] = (err(g, r), KERNEL_TOL)
        for name, (go, gl), (ro, rl) in (("partial_causal", got_pc, ref_pc),
                                         ("partial_full", got_pn, ref_pn)):
            found[name] = (err(go, ro), KERNEL_TOL)
            found[name + "_lse"] = (err(gl, rl), LSE_TOL)
        for name, (e, tol) in found.items():
            print(f"{label} q[1,{s},{hq},{d}] kv heads {hkv} {name}: "
                  f"err {e:.2e} (tol {tol:.0e})", flush=True)
            check(e <= tol, f"{label} {name}: err {e:.3e} > {tol}")
            checks[f"{label}.{name}"] = round(e, 5)
    return {"device": info, "checks": checks, "interpret": interp,
            "setup_s": round(time.monotonic() - t_start, 1)}


def child_train(spec: dict) -> dict:
    """Legs T and M: Trainer over the DFS token file, then a checkpoint."""
    t_start = time.monotonic()
    info = child_device(spec)
    compiles = CompileCounter()
    import jax
    import jax.numpy as jnp
    import hadoop_tpu.native as native
    from hadoop_tpu.fs import FileSystem
    from hadoop_tpu.models.config import get_config
    from hadoop_tpu.ops.attention import attention_impl_traces
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.parallel.mesh import MeshPlan
    from hadoop_tpu.parallel.trainer import Trainer
    check(native.available(), "native library missing in the train child")
    dry, batch = spec["dry"], spec["batch"]
    warmup, steps = 2, 6
    cfg = get_config(spec["preset"])
    plan = MeshPlan(**spec["plan"])
    fs = FileSystem.get(spec["fs"])
    tr = Trainer(cfg, plan, fs, spec["data"], spec["ckpt"] + "-trainer",
                 batch=batch, remat="dots", ckpt_interval=0)
    losses = tr.train(warmup)
    setup_s = time.monotonic() - t_start
    compiled_warm = compiles.count

    t0 = time.perf_counter()
    losses += tr.train(steps)
    wall = time.perf_counter() - t0
    tok_s = steps * batch * cfg.max_seq / wall
    print(f"losses {[round(x, 4) for x in losses]}", flush=True)
    print(f"train {steps} steps of {batch}x{cfg.max_seq} in {wall:.2f}s = "
          f"{tok_s:.0f} tokens/s on {info['count']} x {info['kind']} "
          f"(informational, not a benchmark)", flush=True)
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: step 1 {losses[0]} -> last {losses[-1]}")

    # Which sync really waits? The same window, ended once by
    # block_until_ready and once by a host transfer of the loss.
    def window(sync) -> float:
        rows = [tr.data.next_batch() for _ in range(steps)]
        feed = [(jax.device_put(jnp.asarray(r[:, :-1], jnp.int32),
                                tr.data_sharding),
                 jax.device_put(jnp.asarray(r[:, 1:], jnp.int32),
                                tr.data_sharding)) for r in rows]
        jax.block_until_ready(feed)
        t0 = time.perf_counter()
        for tok, tgt in feed:
            tr.params, tr.opt, m = tr.step_fn(tr.params, tr.opt, tok, tgt)
        sync((tr.params, tr.opt, m))
        return time.perf_counter() - t0

    def by_block(out):
        jax.block_until_ready(out)

    def by_float(out):
        float(out[2]["loss"])

    order = (by_block, by_float, by_float, by_block)
    walls = [window(sync) for sync in order]
    sync_block = (walls[0] + walls[3]) / 2
    sync_float = (walls[1] + walls[2]) / 2
    print(f"sync over {steps} steps: block_until_ready {sync_block:.3f}s, "
          f"float(loss) {sync_float:.3f}s (ratio "
          f"{sync_block / sync_float:.3f}; windows "
          f"{[round(x, 3) for x in walls]})", flush=True)
    # both must wait for the device: one that returns early would read
    # far below the other (a CPU dry run's 0.1 s windows are noise)
    check(dry or 0.9 <= sync_block / sync_float <= 1.1,
          f"the two syncs disagree: block_until_ready {sync_block:.3f}s "
          f"vs float(loss) {sync_float:.3f}s")
    recompiles = compiles.count - compiled_warm
    check(recompiles == 0, f"{recompiles} compiles after warm-up")

    traces = attention_impl_traces()
    print(f"attention implementations traced: {traces}", flush=True)
    if not dry:
        check(traces["causal_flash"] >= 1 and traces["causal_ref"] == 0
              and traces["ring_ref"] == 0,
              f"train-step attention traced to the jnp path: {traces}")

    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.devices()[:plan.n_devices]]
    print(f"bytes_in_use per device: {in_use}", flush=True)
    if not dry:
        check(min(in_use) > 0 and max(in_use) <= 2 * min(in_use),
              f"device memory is not spread evenly: {in_use}")

    t0 = time.monotonic()
    save_checkpoint(fs, spec["ckpt"], tr.step, {"params": tr.params})
    ckpt_s = time.monotonic() - t0
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(tr.params))
    print(f"checkpoint {n_bytes / 1e9:.2f} GB of params -> "
          f"{spec['fs']}{spec['ckpt']} in {ckpt_s:.1f}s", flush=True)
    tr.close()
    return {"device": info, "setup_s": round(setup_s, 1),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "tokens_per_s_info": round(tok_s), "recompiles": recompiles,
            "sync_block_s": round(sync_block, 3),
            "sync_float_s": round(sync_float, 3), "attention": traces,
            "bytes_in_use": in_use, "ckpt_s": round(ckpt_s, 1)}


def child_main(leg: str, spec: dict) -> int:
    fn = {"K": child_kernels, "T": child_train, "M": child_train}[leg]
    print("RESULT " + json.dumps(fn(spec)), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = leg M on one four-chip host")
    ap.add_argument("--dry-cpu", action="store_true",
                    help="debug this script on the CPU backend at 'tiny'; "
                         "waives every device check, proves nothing")
    ap.add_argument("--child", choices=("K", "T", "M"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--spec", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args.child, json.loads(args.spec))
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
