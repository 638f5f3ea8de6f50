"""Long-context serving plane (serving/longctx).

The contract: a prompt too big for one chip's KV pool prefills as a CP
job across the virtual mesh, its KV streams into the cold tiers, and
working-set decode reproduces the single-chip ``decoder.forward``
greedy tokens EXACTLY at small shapes — with the A-B guard rejecting a
deliberately broken ring hop, every longctx shape compiling exactly
once, and the engine's fused-step path untouched beside it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import forward, init_params
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams
from hadoop_tpu.serving.metrics import ServingMetrics


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("tiny", max_seq=512)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _reference_greedy(params, cfg, prompt, n):
    ctx = list(prompt)
    out = []
    for _ in range(n):
        lg = forward(params, jnp.asarray(ctx, jnp.int32)[None, :],
                     cfg)[0, -1]
        tok = int(jnp.argmax(lg))
        out.append(tok)
        ctx.append(tok)
    return out


def _prompt(cfg, n, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=n).tolist()


def _mk_plane(params, cfg, engine, **kw):
    from hadoop_tpu.serving.longctx import LongContextPlane
    kw.setdefault("block_size", engine.block_size)
    kw.setdefault("min_tokens", 100)
    kw.setdefault("max_tokens", 256)
    kw.setdefault("sp", 4)
    kw.setdefault("window_blocks", 3)
    kw.setdefault("tail_tokens", 64)
    kw.setdefault("metrics", engine.metrics)
    return LongContextPlane(params, cfg, engine.kvstore, **kw)


# ------------------------------------------------------- plan / topology

@pytest.mark.parametrize("shape", [(2, 4), (4, 4), (2, 2, 2),
                                   (2, 2, 4), (4, 4, 4), (2, 3, 4)])
def test_ring_order_snakes_through_the_grid(shape):
    """TASP placement: consecutive CP ranks must be physical neighbors
    — on every coordinate grid (2D and the 3D torus-slice shapes) the
    snake order makes every hop one step on one axis."""
    import itertools

    from hadoop_tpu.serving.longctx import ring_order

    class Dev:
        def __init__(self, i, coords):
            self.id = i
            self.coords = coords

    coords = list(itertools.product(*[range(s) for s in shape]))
    devs = [Dev(i, c) for i, c in enumerate(coords)]
    rng = np.random.default_rng(3)
    shuffled = [devs[i] for i in rng.permutation(len(devs))]
    ordered = ring_order(shuffled)
    for a, b in zip(ordered, ordered[1:]):
        dist = sum(abs(x - y) for x, y in zip(a.coords, b.coords))
        assert dist == 1, (
            f"non-neighbor hop {a.coords}->{b.coords} on grid {shape}")


def test_ring_order_without_coords_is_id_order():
    from hadoop_tpu.serving.longctx import ring_order

    class Dev:
        def __init__(self, i):
            self.id = i
            self.coords = None

    devs = [Dev(i) for i in (3, 0, 2, 1)]
    assert [d.id for d in ring_order(devs)] == [0, 1, 2, 3]


def test_choose_sp_mode_validates_and_falls_back(tiny_model):
    from hadoop_tpu.serving.longctx import choose_sp_mode
    _, cfg = tiny_model
    assert choose_sp_mode(cfg, 2, "ulysses") == "ulysses"
    # tiny has 2 kv heads: ulysses over 4 ranks is impossible — loud
    # fallback, not a refused workload
    assert choose_sp_mode(cfg, 4, "ulysses") == "ring"
    with pytest.raises(ValueError):
        choose_sp_mode(cfg, 2, "diagonal")


# ------------------------------------------------------ CP prefill parity

@pytest.mark.parametrize("sp,mode", [(4, "ring"), (2, "ulysses")])
def test_cp_prefill_exact_match(tiny_model, sp, mode):
    """Small-shape A-B: CP last-token logits vs single-chip
    ``decoder.forward`` — exact guard (tight atol + greedy argmax
    identity), for both CP strategies."""
    from hadoop_tpu.serving.longctx import (ContextParallelPrefiller,
                                            run_prefill_ab)
    params, cfg = tiny_model
    prompt = _prompt(cfg, 150)
    pre = ContextParallelPrefiller(params, cfg, block_size=8,
                                   pad_tokens=160, sp=sp, sp_mode=mode)
    report = run_prefill_ab(params, cfg, prompt, pre, mode="exact")
    assert report["accepted"] and report["argmax_agree"]
    assert report["sp_mode"] == mode


def test_cp_prefill_pinned_shape_compiles_once(tiny_model):
    """Different prompt lengths ride ONE padded executable — the
    compile-once contract of the longctx plane."""
    from hadoop_tpu.serving.longctx import ContextParallelPrefiller
    params, cfg = tiny_model
    pre = ContextParallelPrefiller(params, cfg, block_size=8,
                                   pad_tokens=200, sp=4)
    for n in (110, 150, 197):
        res = pre.cp_prefill(_prompt(cfg, n, seed=n))
        list(res.blocks)      # drain the stream
    assert pre.prefill_compiles == 1
    assert pre.head_compiles == 1


def test_guard_rejects_broken_ring_hop(tiny_model, monkeypatch):
    """A deliberately corrupted ring hop (one rank's attention output
    scaled) must be REJECTED by the exact guard — the A-B machinery is
    what stands between a silent CP bug and served logits."""
    import hadoop_tpu.parallel.ring_attention as ra
    from hadoop_tpu.parallel.lowp.guard import ParityGuardError
    from hadoop_tpu.serving.longctx import (ContextParallelPrefiller,
                                            run_prefill_ab)
    params, cfg = tiny_model
    orig = ra.ring_attention

    def broken(q, k, v, axis_name, axis_size, impl="auto"):
        out = orig(q, k, v, axis_name, axis_size, impl)
        rank = jax.lax.axis_index(axis_name)
        return out * jnp.where(rank == 1, 1.5, 1.0)

    monkeypatch.setattr(ra, "ring_attention", broken)
    pre = ContextParallelPrefiller(params, cfg, block_size=8,
                                   pad_tokens=160, sp=4)
    with pytest.raises(ParityGuardError):
        run_prefill_ab(params, cfg, _prompt(cfg, 150), pre,
                       mode="exact")


# ------------------------------------------------------------ end to end

def test_longctx_end_to_end_matches_single_chip(tiny_model):
    """The whole lane: submit through the ENGINE (routing seam), CP
    prefill, KV streamed to the host ring, working-set decode — greedy
    tokens identical to repeated single-chip forward."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, prefill_chunk=8,
                       kv_host_bytes=1 << 22, metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng)
    eng.attach_longctx(plane)
    # the engine's tree is placed for its own step (wqkv); the plane
    # reads the projections by name from the tree it was given
    assert "wqkv" in eng.params["layers"]
    assert "wq" in plane.decoder.params["layers"]
    try:
        prompt = _prompt(cfg, 150)
        req = eng.submit(prompt, SamplingParams(max_new_tokens=6))
        toks = req.wait(180)
        assert toks == _reference_greedy(params, cfg, prompt, 6)
        # the fused step never ran: the monster prompt was the plane's
        assert eng.steps == 0
        st = plane.stats()
        assert st["requests"] == 1
        assert st["blocks_streamed"] == len(prompt) // 8
        kv = eng.kvstore.stats()
        assert kv["chain_ingested"] == len(prompt) // 8
        assert kv["hits_host"] >= len(prompt) // 8
        # working set stays a window+tail, far under the full context
        full_ctx_bytes = (len(prompt) * 2 * cfg.n_layers *
                          cfg.n_kv_heads * cfg.head_dim * 4)
        assert plane.decoder.hbm_working_set_bytes < full_ctx_bytes
        assert st["window_fetches"] > 0
    finally:
        eng.stop()


def test_streamed_chain_feeds_the_radix_path(tiny_model):
    """Interop: a SHORT prompt that is a prefix of a served monster
    prompt maps the longctx-streamed chain through the normal radix
    admission (fetch_cold promotions) — one digest scheme, two
    consumers."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, prefill_chunk=8,
                       kv_host_bytes=1 << 22, metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng)
    eng.attach_longctx(plane)
    try:
        prompt = _prompt(cfg, 150)
        eng.submit(prompt, SamplingParams(max_new_tokens=2)).wait(180)
        short = prompt[:24]
        req = eng.submit(short, SamplingParams(max_new_tokens=3))
        while not req.done.is_set():
            eng.step()
        assert req.wait(0) == _reference_greedy(params, cfg, short, 3)
        assert eng.kvstore.promotions > 0
    finally:
        eng.stop()


def test_short_prompts_keep_the_fused_step(tiny_model):
    """Routing seam: below min_tokens the request rides the fused step
    exactly as before (compile-once intact), at/above it the plane
    serves without touching the step."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, prefill_chunk=8,
                       kv_host_bytes=1 << 20, metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng, min_tokens=100)
    eng.attach_longctx(plane)
    try:
        short = _prompt(cfg, 20)
        req = eng.submit(short, SamplingParams(max_new_tokens=3))
        while not req.done.is_set():
            eng.step()
        assert req.wait(0) == _reference_greedy(params, cfg, short, 3)
        assert eng.decode_compiles == 1
        assert eng.prefill_compiles == 1
        long_req = eng.submit(_prompt(cfg, 120),
                              SamplingParams(max_new_tokens=2))
        long_req.wait(180)
        assert eng.decode_compiles == 1      # untouched by the plane
        assert eng.prefill_compiles == 1
    finally:
        eng.stop()


def test_engine_drain_finishes_longctx_request(tiny_model):
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 20,
                       metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng)
    eng.attach_longctx(plane)
    req = eng.submit(_prompt(cfg, 120), SamplingParams(max_new_tokens=2))
    eng.stop(drain=True, timeout=180.0)
    assert req.done.is_set()
    assert req.state == "FINISHED"
    assert len(req.out_tokens) == 2


# ------------------------------------------ pipelined decode (fused path)

def _prefill_chain(params, cfg, eng, prompt):
    """CP prefill at sp=1 + stream the chain into the engine's tiers —
    the decoder-level fixtures' shared setup (the plane does exactly
    this per request)."""
    from hadoop_tpu.serving.longctx import ContextParallelPrefiller
    pre = ContextParallelPrefiller(params, cfg, block_size=8,
                                   pad_tokens=160, sp=1)
    res = pre.cp_prefill(prompt)
    eng.kvstore.ingest_chain(prompt, res.blocks)
    return res


def _run_decoder(params, cfg, eng, prompt, res, sampling, **kw):
    from hadoop_tpu.serving.longctx.decode import WorkingSetDecoder
    dec = WorkingSetDecoder(params, cfg, eng.kvstore, block_size=8,
                            window_blocks=3, tail_tokens=64, **kw)
    out = []
    dec.paged_decode(prompt, int(np.argmax(res.last_logits)), sampling,
                     tail_k=res.tail_k, tail_v=res.tail_v,
                     deliver=out.append, seed=11,
                     rng=np.random.default_rng(11))
    return out, dec


def test_pipelined_decode_is_token_identical_to_legacy(tiny_model):
    """The fused path's A-B vs the pre-pipelining loop it replaced:
    same chain, same tail, same sampler stream — identical tokens,
    greedy AND stochastic (the pipelined host-sampler fallback draws
    the legacy loop's exact rng stream; the in-graph device sampler is
    greedy-identical by construction). Alongside: the per-token budgets
    the pipelining exists for, audited on the real counters —
    dispatches <= 2 per (token, window) + head, and host->HBM
    transfers counted per (layer, slab), O(chain) instead of the
    legacy loop's O(layers x chain) window slices."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 22,
                       metrics=ServingMetrics())
    try:
        prompt = _prompt(cfg, 150)
        res = _prefill_chain(params, cfg, eng, prompt)
        greedy = SamplingParams(max_new_tokens=6)
        legacy, dl = _run_decoder(params, cfg, eng, prompt, res,
                                  greedy, pipeline=False)
        fused, df = _run_decoder(params, cfg, eng, prompt, res, greedy)
        host, _ = _run_decoder(params, cfg, eng, prompt, res, greedy,
                               sampler="host")
        assert fused == legacy == host and len(fused) == 5
        # stochastic A-B rides the host sampler on both arms
        sp = SamplingParams(max_new_tokens=6, temperature=0.8, top_k=5)
        a, _ = _run_decoder(params, cfg, eng, prompt, res, sp,
                            pipeline=False)
        b, _ = _run_decoder(params, cfg, eng, prompt, res, sp,
                            sampler="host")
        assert a == b
        # ---- budgets (chain = 18 full blocks = 144 tokens)
        chain = (len(prompt) // 8) * 8
        n_win = -(-chain // df.win)
        assert df.dispatches_per_token <= 2 * n_win + 1
        assert df.dispatches < dl.dispatches
        # fetches: one per (layer, slab) on the fused path — the slab
        # IS the transfer unit — one per (layer, window) SLICE legacy
        n_slabs = -(-chain // (df.fetch_windows * df.win))
        assert df.window_fetches == cfg.n_layers * n_slabs * 5
        assert dl.window_fetches == cfg.n_layers * n_win * 5
        assert df.window_fetches < dl.window_fetches
    finally:
        eng.stop()


def test_fused_family_compiles_once_across_tokens(tiny_model):
    """Compile-once on the fused family: a multi-token paged decode —
    across two decoder INSTANCES and both samplers — traces each of
    fstart/fadvance/fwin/ffinish/fhead exactly once (the module-level
    jit cache is per layout family, not per decoder)."""
    from hadoop_tpu.serving.longctx.decode import trace_counts
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 22,
                       metrics=ServingMetrics())
    try:
        prompt = _prompt(cfg, 150)
        res = _prefill_chain(params, cfg, eng, prompt)
        greedy = SamplingParams(max_new_tokens=5)
        _, dec = _run_decoder(params, cfg, eng, prompt, res, greedy)
        _run_decoder(params, cfg, eng, prompt, res, greedy,
                     sampler="host")
        fam = dec._fused.family
        tc = trace_counts()
        for piece in ("fstart", "fadvance", "fwin", "ffinish", "fhead"):
            assert tc[f"{piece}@{fam}"] == 1, (piece, tc)
    finally:
        eng.stop()


def test_int8_longctx_serves_and_guard_accepts(tiny_model):
    """int8-resident CP weights: the plane serves straight off the
    quantized tree (no dequantized second copy), the weight A-B guard
    accepts the arm, and a zeroed payload is REJECTED — the guard is
    falsifiable, not a rubber stamp."""
    from hadoop_tpu.serving.longctx import LongContextPlane
    from hadoop_tpu.serving.weightplane import (WeightPlaneConfig,
                                                dequantize_params,
                                                quantize_params,
                                                run_weight_ab)
    params, cfg = tiny_model
    wp = WeightPlaneConfig(tier="relaxed", quant_embed=True,
                           quant_head=True)
    qparams, rep = quantize_params(params, cfg, wp)
    assert rep["leaves_quantized"] > 0
    ab = run_weight_ab(cfg, params, qparams, wp=wp)
    assert ab["accepted"], ab
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 22,
                       metrics=ServingMetrics())
    plane = LongContextPlane(qparams, cfg, eng.kvstore, block_size=8,
                             min_tokens=100, max_tokens=256, sp=1,
                             window_blocks=3, tail_tokens=64,
                             metrics=eng.metrics)
    try:
        prompt = _prompt(cfg, 150)
        req = plane.longctx_submit(prompt,
                                   SamplingParams(max_new_tokens=4))
        toks = req.wait(180)
        # greedy off the int8 plane == greedy off the dequantized
        # reconstruction (numerically what qdot contracts against)
        assert toks == _reference_greedy(
            dequantize_params(qparams, cfg), cfg, prompt, 4)
        st = plane.stats()
        assert st["int8_weights"] is True
        assert st["dequantized_view_bytes"] == 0
    finally:
        plane.stop()
        eng.stop()
    # falsifiability: zero one layer matmul's payload -> rejected
    broken = dict(qparams)
    broken["layers"] = dict(qparams["layers"])
    wq = qparams["layers"]["wq"]
    broken["layers"]["wq"] = {"q": np.zeros_like(wq["q"]),
                              "s": wq["s"]}
    assert not run_weight_ab(cfg, params, broken, wp=wp)["accepted"]
    # the legacy loop cannot serve a quantized tree: loud, not wrong
    from hadoop_tpu.serving.longctx.decode import WorkingSetDecoder
    with pytest.raises(ValueError, match="pipeline"):
        WorkingSetDecoder(qparams, cfg, eng.kvstore, block_size=8,
                          pipeline=False)


def test_hbm_ledger_reflects_decode_double_buffer(tiny_model):
    """Live HBM ledger: the pipelined decoder's window component is
    BOTH in-flight slabs of the double buffer (2x one window at the
    default slab depth), the in-graph sampler registers its device
    state, /v1/health surfaces the same split, and stop() unregisters
    every owner — a stopped plane never haunts /prom."""
    from hadoop_tpu.obs.hbm import hbm_ledger
    from hadoop_tpu.serving.longctx.decode import WorkingSetDecoder
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 20,
                       metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng, sp=1)
    eng.attach_longctx(plane)
    try:
        dec = plane.decoder
        assert dec.fetch_windows == cfg.n_layers
        # slab depth = n_layers => one slab costs exactly one window
        # of per-token working-set bytes; the double buffer costs two
        assert dec.hbm_window_bytes == 2 * dec.win * dec._per_tok_bytes
        assert dec.hbm_working_set_bytes == (
            dec.hbm_window_bytes + dec.tail_cap * dec._per_tok_bytes
            + dec.sampler_state_bytes)
        comps = hbm_ledger().report()["components"]
        assert comps["longctx_window"] == dec.hbm_window_bytes
        assert comps["longctx_tail"] == \
            dec.tail_cap * dec._per_tok_bytes
        assert comps["longctx_sampler"] == dec.sampler_state_bytes > 0
        from hadoop_tpu.conf import Configuration
        from hadoop_tpu.serving.server import ServingServer
        srv = ServingServer(eng, Configuration(load_defaults=False))
        _, health = srv._health({}, b"")
        assert health["hbm"]["components"]["longctx_window"] == \
            dec.hbm_window_bytes
        # the legacy loop keeps the pre-pipelining accounting: one
        # window in flight, no device sampler state
        dl = WorkingSetDecoder(params, cfg, eng.kvstore, block_size=8,
                               window_blocks=3, tail_tokens=64,
                               pipeline=False)
        assert dl.hbm_window_bytes == dl.win * dl._per_tok_bytes
        assert dl.sampler_state_bytes == 0
    finally:
        eng.stop()
    comps = hbm_ledger().report()["components"]
    assert "longctx_window" not in comps
    assert "longctx_sampler" not in comps


def test_plane_from_conf_reads_decode_pipeline_keys(tiny_model):
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.serving.longctx import longctx_plane_from_conf
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 20,
                       metrics=ServingMetrics())
    try:
        conf = Configuration(load_defaults=False)
        conf.set("serving.parity", "relaxed")
        conf.set("serving.longctx.min.tokens", "100")
        conf.set("serving.longctx.chips", "1")
        conf.set("serving.longctx.decode.pipeline", "false")
        conf.set("serving.longctx.decode.sampler", "host")
        plane = longctx_plane_from_conf(conf, cfg, eng)
        assert plane.decoder.pipeline is False
        assert plane.decoder.sampler == "host"
        plane.stop()
        conf.set("serving.longctx.decode.pipeline", "true")
        conf.set("serving.longctx.decode.fetch.windows", "2")
        plane = longctx_plane_from_conf(conf, cfg, eng)
        assert plane.decoder.pipeline is True
        assert plane.decoder.fetch_windows == 2
        plane.stop()
        conf.set("serving.longctx.decode.sampler", "bogus")
        with pytest.raises(ValueError, match="sampler"):
            longctx_plane_from_conf(conf, cfg, eng)
    finally:
        eng.stop()


# ------------------------------------------------------------ validation

def test_longctx_submit_validation(tiny_model):
    """Requests the plane can NEVER serve fail loudly at submit (the
    door's 400), not as a wedged worker."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 20,
                       metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng, sp=1, tail_tokens=16)
    eng.attach_longctx(plane)
    try:
        with pytest.raises(ValueError, match="max.tokens"):
            eng.submit(_prompt(cfg, 300),
                       SamplingParams(max_new_tokens=2))
        with pytest.raises(ValueError, match="tail"):
            eng.submit(_prompt(cfg, 120),
                       SamplingParams(max_new_tokens=32))
    finally:
        eng.stop()


def test_host_ring_too_small_for_chain_is_loud(tiny_model):
    params, cfg = tiny_model
    # a ring that holds ~4 blocks cannot hold a 15-block chain and
    # there is no DFS tier behind it — reject at the door
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64,
                       kv_host_bytes=4 * 2 * cfg.n_layers * 8 *
                       cfg.n_kv_heads * cfg.head_dim * 4,
                       metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng, sp=1)
    eng.attach_longctx(plane)
    try:
        with pytest.raises(ValueError, match="host-ring|host.ring|ring"):
            eng.submit(_prompt(cfg, 130),
                       SamplingParams(max_new_tokens=2))
    finally:
        eng.stop()


def test_plane_requires_cold_tier(tiny_model):
    from hadoop_tpu.serving.longctx import LongContextPlane
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64)
    try:
        with pytest.raises(ValueError, match="cold|host|dfs"):
            LongContextPlane(params, cfg, eng.kvstore, block_size=8,
                             min_tokens=100)
    finally:
        eng.stop()


def test_plane_from_conf_requires_relaxed_parity(tiny_model):
    """The tier gate: under the bitwise default the plane must be
    unconstructable — CP softmax reassociation is not bitwise."""
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.serving.longctx import longctx_plane_from_conf
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 20,
                       metrics=ServingMetrics())
    try:
        conf = Configuration(load_defaults=False)
        with pytest.raises(ValueError, match="relaxed"):
            longctx_plane_from_conf(conf, cfg, eng)
        conf.set("serving.parity", "relaxed")
        conf.set("serving.longctx.min.tokens", "100")
        conf.set("serving.longctx.chips", "2")
        plane = longctx_plane_from_conf(conf, cfg, eng)
        assert plane.min_tokens == 100
        assert plane.prefiller.sp == 2
        plane.stop()
    finally:
        eng.stop()


def test_health_exposes_longctx_stats(tiny_model):
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.serving.server import ServingServer
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                       max_context=64, kv_host_bytes=1 << 20,
                       metrics=ServingMetrics())
    plane = _mk_plane(params, cfg, eng, sp=1)
    eng.attach_longctx(plane)
    srv = ServingServer(eng, Configuration(load_defaults=False))
    try:
        status, health = srv._health({}, b"")
        assert status == 200
        assert health["longctx"]["enabled"] is True
        assert health["longctx"]["chips"] == 1
    finally:
        eng.stop()
    # a bitwise replica reports the plane absent
    plain = DecodeEngine(params, cfg, max_batch=2, block_size=8,
                         max_context=64)
    assert plain.longctx_stats() == {"enabled": False}
    plain.stop()


# ------------------------------------------- router prefill capacity gate

def _rec(path, role, **attrs):
    from hadoop_tpu.registry import ServiceRecord
    a = {"state": "serving", "role": role}
    a.update({k: str(v) for k, v in attrs.items()})
    return ServiceRecord(path, {"http": "127.0.0.1:9"}, a)


def _router(conf=None):
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.serving.router import ServingRouter
    conf = conf or Configuration(load_defaults=False)
    conf.set("serving.router.prefill.min.tokens", "8")
    return ServingRouter(("127.0.0.1", 1), "svc", conf)


def test_router_skips_undersized_prefill_replica(monkeypatch):
    """The capacity gate: a monster prompt is never OFFERED to a
    prefill replica whose advertised HBM pool cannot hold its paged
    working set — loud skip with a counter, not a handoff failure.
    (The host ring backs demotions, not admissions, so it does NOT
    count toward prefill capacity.)"""
    r = _router()
    # pool of 4 blocks x 4 tokens = 16 tokens; a fat host ring must
    # not make a 100-token prompt look admittable
    small = _rec("/services/serving/svc/small", "prefill",
                 kv_block_bytes=1024, kv_block_size=4, kv_hbm_blocks=4,
                 kv_host_bytes=1 << 30)
    dec = _rec("/services/serving/svc/dec", "decode")
    monkeypatch.setattr(r, "replicas",
                        lambda refresh=False: [small, dec])
    posts = []
    monkeypatch.setattr(r, "_post",
                        lambda *a, **k: posts.append(a) or {})
    shipped = r._maybe_offload_prefill(
        {"tokens": list(range(100))}, None)
    assert shipped is False
    assert r.prefill_capacity_skips == 1
    assert posts == []
    r.close()


def test_router_offloads_to_the_replica_that_fits(monkeypatch):
    r = _router()
    small = _rec("/services/serving/svc/small", "prefill",
                 kv_block_bytes=1024, kv_block_size=4, kv_hbm_blocks=4,
                 kv_host_bytes=0)
    big = _rec("/services/serving/svc/big", "prefill",
               kv_block_bytes=1024, kv_block_size=4, kv_hbm_blocks=64,
               kv_host_bytes=0)
    dec = _rec("/services/serving/svc/dec", "decode")
    monkeypatch.setattr(r, "replicas",
                        lambda refresh=False: [small, big, dec])
    posts = []
    monkeypatch.setattr(
        r, "_post",
        lambda rec, *a, **k: posts.append(rec.path) or
        {"persisted_tokens": 100})
    assert r._maybe_offload_prefill({"tokens": list(range(100))},
                                    None) is True
    assert posts == ["/services/serving/svc/big"]
    assert r.prefill_capacity_skips == 1
    r.close()


def test_router_longctx_replica_is_never_capacity_skipped(monkeypatch):
    """A replica advertising the long-context plane + DFS streams
    monster prompts into the cold tiers — its tiny HBM pool must not
    disqualify it (that pool is exactly what longctx works around)."""
    r = _router()
    lcx = _rec("/services/serving/svc/lcx", "prefill",
               kv_block_bytes=1024, kv_block_size=4, kv_hbm_blocks=4,
               kv_host_bytes=0, longctx=1, kv_dfs=1)
    dec = _rec("/services/serving/svc/dec", "decode")
    monkeypatch.setattr(r, "replicas",
                        lambda refresh=False: [lcx, dec])
    posts = []
    monkeypatch.setattr(
        r, "_post",
        lambda rec, *a, **k: posts.append(rec.path) or
        {"persisted_tokens": 100000})
    assert r._maybe_offload_prefill({"tokens": list(range(100000))},
                                    None) is True
    assert posts == ["/services/serving/svc/lcx"]
    assert r.prefill_capacity_skips == 0
    r.close()


def test_router_respects_longctx_pinned_budget(monkeypatch):
    """...but only up to the plane's advertised pinned prompt budget:
    past serving.longctx.max.tokens the replica's door rejects, so the
    gate must skip rather than burn a doomed handoff."""
    r = _router()
    lcx = _rec("/services/serving/svc/lcx", "prefill",
               kv_block_bytes=1024, kv_block_size=4, kv_hbm_blocks=4,
               longctx=1, kv_dfs=1, longctx_max_tokens=4096)
    dec = _rec("/services/serving/svc/dec", "decode")
    monkeypatch.setattr(r, "replicas",
                        lambda refresh=False: [lcx, dec])
    posts = []
    monkeypatch.setattr(r, "_post",
                        lambda *a, **k: posts.append(a) or {})
    assert r._maybe_offload_prefill({"tokens": list(range(5000))},
                                    None) is False
    assert posts == []
    assert r.prefill_capacity_skips == 1
    r.close()


def test_router_keeps_legacy_records_eligible(monkeypatch):
    """Records without capacity attributes (hand-registered,
    mid-upgrade) must stay eligible — a stricter router cannot starve
    an older fleet."""
    r = _router()
    legacy = _rec("/services/serving/svc/old", "prefill")
    dec = _rec("/services/serving/svc/dec", "decode")
    monkeypatch.setattr(r, "replicas",
                        lambda refresh=False: [legacy, dec])
    posts = []
    monkeypatch.setattr(
        r, "_post",
        lambda rec, *a, **k: posts.append(rec.path) or
        {"persisted_tokens": 8})
    assert r._maybe_offload_prefill({"tokens": list(range(50))},
                                    None) is True
    assert posts and r.prefill_capacity_skips == 0
    r.close()
