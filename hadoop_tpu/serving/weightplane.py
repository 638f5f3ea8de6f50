"""The serving weight plane: per-tensor dtype/layout policy for
resident model weights.

PROFILE.md's measured wall is HBM, not FLOPs: flagship-1b serving caps
at batch 2 and decode is memory-bandwidth-bound, yet every serving
matmul reads f32-resident weights. ISSUE 10's lowp plane quantized the
*wires* (grad buckets, TP reduces, KV blocks); this module extends the
same quantization story to resident state (the Flash Communication
direction, arXiv:2412.04964, applied where the fleet actually spends):
int8 weights + per-group f32 scales live in HBM, dequantized
in-register inside each matmul, and the ~4x of freed HBM converts
directly into more decode lanes x context at fixed chip memory
(the engine sizes its KV pool against the MEASURED resident bytes).

Tiering mirrors ``parallel.parity``:

- ``serving.parity=bitwise`` (the default): the loader places the
  checkpoint's f32/bf16 leaves untouched and ZERO code in this module
  is reachable from the engine's compiled step — enforced statically
  by tpulint's ``parity/relaxed-gated`` checker (the in-graph entry
  points here, :func:`qdot` / :func:`qrows` / :func:`qhead`, and the
  load-time :func:`quantized_load`, must sit under a lexical guard
  naming the relaxed tier at every call site outside this module).
- ``serving.parity=relaxed``: matmul weights are int8 with per-group
  scales. Values are allclose, never bitwise; acceptance is the
  logits/output A-B guard (:func:`run_weight_ab` — same machinery
  family as ``lowp/guard.py``'s ``run_loss_ab``: same inputs through
  both planes, bounded divergence, verdict recorded as a plain dict).

One quantizer defines every int8 surface (the kvstore ``codec.py``
precedent): the host-side per-group codec here IS
``parallel/lowp/quant.py``'s public ``quantize_array`` /
``dequantize_array`` pair — weight groups ride the contraction
dimension so the scales dequantize next to the MXU.

Layout: a weight that contracts over its dimension ``D`` (``x @ w``
with ``w [D, N]``) is stored transposed-and-grouped as
``{"q": int8 [N, G, gs], "s": f32 [N, G]}`` with ``G * gs == D`` —
one scale per (output column, input group), the GPTQ/AWQ-style
weight-only grouping. Embedding rows ([V, D], a gather not a matmul)
group along D without the transpose so a row dequantizes in one fused
multiply. Norm weights, biases and ``pos_embed`` never quantize (they
are bytes-irrelevant and value-critical).

Quantize-at-load streams per shard: the loader's concurrent shard
fetch feeds :func:`make_load_quantizer` one assembled leaf at a time
(``load_checkpoint(leaf_transform=...)``), the f32 buffer is dropped
the moment its int8 twin exists, so peak host RAM during a quantized
load stays bounded by the LARGEST leaf, never the full f32 model —
``report["peak_f32_bytes"]`` records the measured bound.

Conf keys (read by :func:`weightplane_from_conf`):

  serving.parity                    bitwise | relaxed  (default bitwise)
  serving.weights.codec             int8               (the wired codec)
  serving.weights.group             default 64   (elements per scale
                                    group along the contraction dim;
                                    must divide every contraction dim)
  serving.weights.embed             default false (quantize embedding)
  serving.weights.head              default false (quantize LM head;
                                    tied embeddings quantize as one)
  serving.weights.guard.min-agree   default 0.95 (greedy argmax
                                    agreement floor of the A-B guard)
  serving.weights.guard.rel-tol     default 0.25 (max |logit err| /
                                    std(reference logits))
  serving.kv.hbm.bytes              default 0    (engine HBM budget:
                                    KV pool + lanes sized against the
                                    measured resident weight bytes)
"""

from __future__ import annotations

import dataclasses
import re
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.parallel.lowp.quant import dequantize_array, quantize_array

WEIGHTS_PARITY_KEY = "serving.parity"
TIERS = ("bitwise", "relaxed")

# the per-layer matmul weights: every one contracts x over its -2 axis
# (x @ w), so all of them store transposed-and-grouped. On a MoE config
# the same three FFN names carry the layer-stacked EXPERT stacks
# ([L, E, D, F] / [L, E, F, D]): quantize_weight groups the trailing
# contraction dim under any leading axes, so ONE policy table covers
# dense and sparse — per-expert int8 payloads + per-(expert, column)
# scale groups. The ROUTER stays f32 on purpose: it is value-critical
# (a flipped top-k re-routes whole tokens, not a bounded perturbation)
# and bytes-irrelevant next to the expert stacks — the norms precedent.
LAYER_MATMULS = frozenset({
    "wq", "wk", "wv", "wo",
    "w_gate", "w_up", "w_down",          # swiglu mlp / MoE expert stacks
    "w_in", "w_out",                     # gelu mlp (biases stay f32)
})

# the expert FFN stacks of a MoE layer — the subset of LAYER_MATMULS
# whose resident bytes the engine ledgers under the dedicated
# ``moe_experts`` HBM component and shards along the expert dim
EXPERT_STACKS = frozenset({"w_gate", "w_up", "w_down"})

_QKEYS = frozenset({"q", "s"})
_KEYSTR = re.compile(r"\['([^']+)'\]")


@dataclasses.dataclass(frozen=True)
class WeightPlaneConfig:
    """Static weight-plane policy, fixed at load time.

    ``tier == "bitwise"`` disables everything: the loader never calls
    the quantizer and the engine's compiled step contains zero
    weightplane code. The per-tensor flags describe what the relaxed
    tier quantizes, not whether the tier is on.
    """
    tier: str = "bitwise"
    codec: str = "int8"
    group: int = 64                  # elements per scale group (contraction dim)
    quant_embed: bool = False
    quant_head: bool = False
    guard_min_agree: float = 0.95
    guard_rel_tol: float = 0.25

    def __post_init__(self):
        if self.tier not in TIERS:
            raise ValueError(f"{WEIGHTS_PARITY_KEY} must be one of "
                             f"{TIERS}, got {self.tier!r}")
        if self.codec != "int8":
            raise ValueError(f"serving.weights.codec: only 'int8' is "
                             f"wired, got {self.codec!r}")
        if self.group < 1:
            raise ValueError(f"serving.weights.group must be >= 1, "
                             f"got {self.group}")

    @property
    def relaxed(self) -> bool:
        return self.tier == "relaxed"


BITWISE_WEIGHTS = WeightPlaneConfig()


def weightplane_from_conf(conf) -> WeightPlaneConfig:
    """Build a WeightPlaneConfig from a Configuration (defaults above)."""
    if conf is None:
        return BITWISE_WEIGHTS
    return WeightPlaneConfig(
        tier=conf.get(WEIGHTS_PARITY_KEY, "bitwise"),
        codec=conf.get("serving.weights.codec", "int8"),
        group=conf.get_int("serving.weights.group", 64),
        quant_embed=conf.get_bool("serving.weights.embed", False),
        quant_head=conf.get_bool("serving.weights.head", False),
        guard_min_agree=conf.get_float("serving.weights.guard.min-agree",
                                       0.95),
        guard_rel_tol=conf.get_float("serving.weights.guard.rel-tol",
                                     0.25))


# ------------------------------------------------------- the weight codec

def quantize_weight(arr, group: int, *, transpose: bool) -> Dict[str, Any]:
    """One weight leaf -> ``{"q": int8 [..., G, gs], "s": f32 [..., G]}``.

    ``transpose=True`` swaps the last two axes first so the group axis
    is the CONTRACTION dimension of ``x @ w`` (matmul weights store
    ``[.., N, D]``-major); embedding-style rows ([V, D], contraction
    already last) pass ``transpose=False``. The quantizer is
    ``lowp.quant.quantize_array`` — the one public per-group int8
    codec — applied at full +/-127 range (resident weights accumulate
    nothing in-wire, so no headroom is carved out).

    Loud failure on a group/shape mismatch: a contraction dim the
    group does not divide raises instead of silently regrouping
    across rows, which would dequantize against the wrong scales.
    """
    a = np.asarray(arr)
    if transpose:
        a = np.swapaxes(a, -1, -2)
    gs = int(group)
    d = a.shape[-1] if a.ndim else 0
    if a.ndim < 1 or d % gs != 0:
        raise ValueError(
            f"serving.weights.group={gs} does not divide the "
            f"contraction dim {d} of a weight with shape "
            f"{tuple(np.shape(arr))} — pick a group that divides every "
            f"quantized contraction dimension")
    q, s = quantize_array(np.ascontiguousarray(a, np.float32), codec="int8",
                          group=gs)
    g = d // gs
    lead = a.shape[:-1]
    return {"q": q.reshape(*lead, g, gs),
            "s": s.reshape(*lead, g)}


def dequantize_weight(qw: Dict[str, Any], *, transpose: bool,
                      dtype=np.float32) -> np.ndarray:
    """Inverse of :func:`quantize_weight` (values are the int8
    reconstruction, allclose to — never bitwise — the original)."""
    q = np.asarray(qw["q"])
    s = np.asarray(qw["s"])
    *lead, g, gs = q.shape
    if tuple(s.shape) != tuple(lead) + (g,):
        raise ValueError(f"weight scale plane {s.shape} does not match "
                         f"quantized payload {q.shape} (expected "
                         f"{tuple(lead) + (g,)})")
    out = dequantize_array(q.reshape(-1, gs), s.reshape(-1),
                           tuple(lead) + (g * gs,), dtype)
    if transpose:
        out = np.swapaxes(out, -1, -2)
    return np.ascontiguousarray(out)


def is_qtensor(leaf) -> bool:  # lint: static-fn — pytree structure
    """Is this params-tree node a quantized weight? Structure, not
    values: static at trace time (the fused decode jits branch on it
    to pick the weight route per family)."""
    return isinstance(leaf, dict) and set(leaf.keys()) == _QKEYS


def is_quantized_tree(params) -> bool:
    """Does any leaf of ``params`` carry the quantized layout?"""
    def walk(node) -> bool:
        if is_qtensor(node):
            return True
        if isinstance(node, dict):
            return any(walk(v) for v in node.values())
        return False
    return walk(params)


def resident_weight_bytes(params) -> int:
    """MEASURED resident bytes of a params tree — int8 payloads count
    one byte per element, scale planes four; this is the number the
    engine budgets its KV pool and decode lanes against."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        total += int(np.prod(np.shape(leaf))) * \
            jnp.dtype(leaf.dtype).itemsize
    return total


def describe_tree(params) -> Dict[str, Any]:
    """Weight-plane summary for /v1/health, the registry record and
    bench JSON: resident dtype, measured bytes, quantized-leaf count."""
    leaves = jax.tree_util.tree_leaves(params)
    n_int8 = sum(1 for x in leaves if jnp.dtype(x.dtype) == jnp.int8)
    quantized = is_quantized_tree(params)
    if quantized:
        dtype = "int8"
    else:
        dtype = str(np.dtype(leaves[0].dtype)) if leaves else "none"
    return {"dtype": dtype, "quantized": quantized,
            "weight_bytes": resident_weight_bytes(params),
            "int8_leaves": n_int8, "leaves": len(leaves)}


# --------------------------------------------------------- policy + apply

def _resolve_flags(cfg: ModelConfig,
                   wp: WeightPlaneConfig) -> Tuple[bool, bool]:
    """(quant_embed, quant_head) with the tied-embedding coupling
    resolved: a tied model has ONE matrix serving both surfaces, so the
    two flags must agree — quantizing "just the head" of a tied model
    would quantize the gather too, silently."""
    if cfg.tie_embeddings and wp.quant_head != wp.quant_embed:
        raise ValueError(
            "serving.weights.embed and serving.weights.head must match "
            "on a tied-embeddings model (one matrix serves both)")
    return wp.quant_embed, wp.quant_head


def _quantize_one(key: str, arr, *, in_layers: bool, cfg: ModelConfig,
                  wp: WeightPlaneConfig, report: Dict[str, Any]):
    """Apply the per-tensor policy to one leaf; returns the (possibly
    quantized) leaf and updates the running load report."""
    q_embed, q_head = report["_flags"]
    t0 = time.monotonic()
    if in_layers and key in LAYER_MATMULS:
        out = quantize_weight(arr, wp.group, transpose=True)
    elif key == "embed" and q_embed:
        out = quantize_weight(arr, wp.group, transpose=False)
    elif key == "lm_head" and q_head:
        out = quantize_weight(arr, wp.group, transpose=True)
    else:
        return arr
    report["quantize_seconds"] += time.monotonic() - t0
    report["leaves_quantized"] += 1
    return out


def _fresh_report(cfg: ModelConfig,
                  wp: WeightPlaneConfig) -> Dict[str, Any]:
    if not wp.relaxed:
        # the module contract, enforced here and not by call-site
        # discipline: the bitwise tier NEVER quantizes — a bitwise
        # config reaching the quantizer is a wiring bug upstream
        raise ValueError(
            f"{WEIGHTS_PARITY_KEY}={wp.tier!r} must be 'relaxed' to "
            f"quantize resident weights (the bitwise tier loads the "
            f"checkpoint's own dtypes untouched)")
    return {"tier": wp.tier, "codec": wp.codec, "group": wp.group,
            "quant_embed": wp.quant_embed, "quant_head": wp.quant_head,
            "leaves_quantized": 0, "quantize_seconds": 0.0,
            "total_f32_bytes": 0, "peak_f32_bytes": 0,
            "moe_experts": cfg.n_experts if cfg.is_moe else 0,
            "_flags": _resolve_flags(cfg, wp)}


def _finish_report(report: Dict[str, Any], params) -> Dict[str, Any]:
    report.pop("_flags", None)
    report["quantize_seconds"] = round(report["quantize_seconds"], 3)
    report["weight_bytes"] = resident_weight_bytes(params)
    if report.get("moe_experts"):
        report["expert_bytes"] = _expert_stack_bytes(params)
    return report


def _expert_stack_bytes(params) -> int:
    """The routed experts' stacks, wherever the tree keeps its expert
    layers: ``layers`` (one stack of like layers) or ``moe_layers`` (the
    expert layers of a tree with a stack per kind; its shared expert is
    dense remainder)."""
    if not isinstance(params, dict):
        return 0
    layers = params.get("moe_layers") or params.get("layers", {})
    return sum(resident_weight_bytes(layers[k])
               for k in EXPERT_STACKS if k in layers)


def expert_weight_bytes(params, cfg: ModelConfig) -> int:
    """MEASURED resident bytes of the expert FFN stacks (0 on a dense
    config) — what the engine ledgers under the ``moe_experts`` HBM
    component, beside (not inside) the dense ``weights`` remainder."""
    if not cfg.is_moe:
        return 0
    return _expert_stack_bytes(params)


def expert_shard_count(n_experts: int, requested: int,
                       n_devices: int) -> int:
    """Resolve ``serving.moe.shards``: how many chips the expert dim
    splits across. ``requested=0`` (auto) picks the largest shard count
    the replica's devices allow that divides the expert count; an
    explicit request that does not divide the experts or exceeds the
    devices is a loud error, never a silent round-down."""
    if n_experts <= 0:
        return 1
    if requested:
        if requested > n_devices:
            raise ValueError(
                f"serving.moe.shards={requested} exceeds the replica's "
                f"{n_devices} local device(s)")
        if n_experts % requested:
            raise ValueError(
                f"serving.moe.shards={requested} does not divide "
                f"n_experts={n_experts} — expert shards must be equal")
        return int(requested)
    for d in range(min(n_devices, n_experts), 0, -1):
        if n_experts % d == 0:
            return d
    return 1


def quantize_params(params, cfg: ModelConfig,
                    wp: WeightPlaneConfig) -> Tuple[dict, Dict[str, Any]]:
    """In-memory policy application: a loaded f32 params tree -> its
    weight-plane form + the load report (the bench/test twin of the
    streaming :func:`quantized_load` — both run the same per-leaf
    transform, so the two paths can never disagree on policy)."""
    report = _fresh_report(cfg, wp)
    out: Dict[str, Any] = {}
    for key, val in params.items():
        if key == "layers":
            out["layers"] = {
                lk: _quantize_one(lk, lv, in_layers=True, cfg=cfg,
                                  wp=wp, report=report)
                for lk, lv in val.items()}
        else:
            out[key] = _quantize_one(key, val, in_layers=False, cfg=cfg,
                                     wp=wp, report=report)
    return out, _finish_report(report, out)


def _leaf_key(name: str) -> Tuple[str, bool]:
    """(trailing key, under-"layers") of a checkpoint keystr like
    ``['params']['layers']['wq']``."""
    keys = _KEYSTR.findall(name)
    if not keys:
        return name, False
    return keys[-1], "layers" in keys[:-1]


def make_load_quantizer(cfg: ModelConfig, wp: WeightPlaneConfig
                        ) -> Tuple[Callable, Dict[str, Any]]:
    """The streaming form of :func:`quantize_params`: a
    ``leaf_transform`` for ``load_checkpoint`` that quantizes each
    assembled leaf the moment its shards arrive, so the full f32 model
    is never resident on the host. The shared ``report`` dict fills in
    as leaves stream through; ``peak_f32_bytes`` tracks the measured
    high-water mark of live float bytes (the assembled leaf plus its
    in-flight shard payloads — ~2x the largest leaf, a hard bound far
    below the full model)."""
    report = _fresh_report(cfg, wp)

    def transform(name: str, arr: np.ndarray):
        key, in_layers = _leaf_key(name)
        f32 = int(arr.nbytes)
        report["total_f32_bytes"] += f32
        # the raw shard bytes of THIS leaf are still referenced by the
        # caller while we transform — count both sides of the copy
        report["peak_f32_bytes"] = max(report["peak_f32_bytes"], 2 * f32)
        return _quantize_one(key, arr, in_layers=in_layers, cfg=cfg,
                             wp=wp, report=report)

    return transform, report


def quantized_load(fs, base_dir: str, cfg: ModelConfig,
                   wp: WeightPlaneConfig, *, step: Optional[int] = None,
                   io_workers: int = 4):
    """Quantize-at-load from the DFS checkpoint shards: the loader's
    concurrent shard fetch feeds the quantizer one leaf at a time (see
    ``parallel.checkpoint.load_checkpoint``'s ``leaf_transform``
    streaming mode). Returns ``(params, step, report)``; ``report``
    carries ``quantize_seconds``, the measured ``weight_bytes`` and the
    streaming peak. RELAXED-TIER ENTRY POINT: call sites outside this
    module must sit under a lexical relaxed-parity guard."""
    from hadoop_tpu.serving.loader import load_serving_params
    transform, report = make_load_quantizer(cfg, wp)
    t0 = time.monotonic()
    params, step = load_serving_params(fs, base_dir, cfg, step=step,
                                       io_workers=io_workers,
                                       leaf_transform=transform)
    _finish_report(report, params)
    report["load_seconds"] = round(time.monotonic() - t0, 3)
    return params, step, report


def dequantize_params(qparams, cfg: ModelConfig) -> dict:
    """The f32 reconstruction of a weight-plane tree (guard/test use:
    ``forward(dequantize_params(q))`` computes exactly the floats the
    engine's in-graph dequantizing matmuls contract against)."""
    dt = cfg.jax_dtype

    def walk(node, key: str):
        if is_qtensor(node):
            # every quantized leaf stores transposed except the
            # embedding matrix (a row gather, contraction already last)
            return jnp.asarray(dequantize_weight(
                node, transpose=key != "embed", dtype=dt))
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        return node

    return walk(qparams, "")


# ------------------------------------------------- in-graph entry points
# (RELAXED-TIER ENTRY POINTS: tpulint's parity/relaxed-gated checker
# requires every call site outside this module to sit under a lexical
# guard naming the relaxed tier, so serving.parity=bitwise provably
# compiles zero quantized code.)

def qdot(x, qw):
    """Weight-only int8 matmul: ``x [..., D] @ w`` against a quantized
    weight ``{"q": int8 [N, G, gs], "s": f32 [N, G]}``. The dequantize
    (one multiply per int8 element) happens in-register next to the
    contraction — XLA fuses the convert+scale into the matmul operand
    read, so HBM only ever moves the int8 payload + the scale plane."""
    q, s = qw["q"], qw["s"]
    n = q.shape[0]
    w = (q.astype(jnp.float32) * s[..., None]).reshape(n, -1)
    return jnp.einsum("...d,nd->...n", x, w.astype(x.dtype))


def qrows(qe, tokens, dtype):
    """Quantized embedding gather: int8 rows + their scale groups are
    gathered and dequantized per token (``qe`` = {"q": [V, G, gs],
    "s": [V, G]})."""
    q = qe["q"][tokens]
    s = qe["s"][tokens]
    rows = q.astype(jnp.float32) * s[..., None]
    return rows.reshape(*rows.shape[:-2], -1).astype(dtype)


def qslice(qw, l):
    """Layer ``l``'s slice of a layer-stacked quantized weight — the
    quantized twin of ``layers["wq"][l]``: both planes slice their
    leading ``n_layers`` dim together so the scales can never pair
    with another layer's payload. In-graph (``l`` may be a traced
    index, as in the longctx decoder's per-layer dispatches)."""
    return {"q": qw["q"][l], "s": qw["s"][l]}


def qhead(params, h, cfg: ModelConfig):
    """Quantized LM head: ``h [..., D] @ head [D, V]`` where the head
    is the (transposed-stored) quantized ``lm_head`` — or the quantized
    ``embed`` matrix when embeddings are tied (one tensor, both
    surfaces, same int8 bytes). Delegates to :func:`qdot` so the head
    contraction can never drift from the layer matmuls'."""
    return qdot(h, params["embed"] if cfg.tie_embeddings
                else params["lm_head"])


def qedot(x, qw):
    """Expert-batched int8 matmul: ``x [E, C, D]`` against a quantized
    expert stack ``{"q": int8 [E, N, G, gs], "s": f32 [E, N, G]}`` —
    the MoE twin of :func:`qdot`, one contraction per expert with that
    expert's own scale plane (scales can never cross experts). Covers
    both orientations of the stacks: w_gate/w_up store [E, F, D]
    (contract D), w_down stores [E, D, F] (contract F) — the stored
    trailing dim is always the contraction dim, exactly as for qdot."""
    q, s = qw["q"], qw["s"]
    e, n = q.shape[0], q.shape[1]
    w = (q.astype(jnp.float32) * s[..., None]).reshape(e, n, -1)
    return jnp.einsum("ecd,end->ecn", x, w.astype(x.dtype))


# -------------------------------------------------- logits/output guard

def weight_ab_report(logits_ref, logits_q, *, min_agree: float = 0.95,
                     rel_tol: float = 0.25) -> Dict[str, Any]:
    """Accept/reject the quantized weight plane from two teacher-forced
    logit tensors over identical inputs (the serving twin of
    ``lowp.guard.loss_curve_report``: same inputs through both planes,
    bounded divergence, a plain-dict verdict the bench records).

    Accepted iff (a) both tensors are finite, (b) the per-position
    greedy argmax agrees on at least ``min_agree`` of positions
    (teacher-forced, so one flip never compounds into the next
    position), and (c) the max absolute logit error stays within
    ``rel_tol`` of the reference logit spread (std) — quantization
    noise must stay a perturbation, never a re-ranking of the whole
    distribution."""
    a = np.asarray(logits_ref, np.float64)
    b = np.asarray(logits_q, np.float64)
    report: Dict[str, Any] = {"min_agree": min_agree, "rel_tol": rel_tol,
                              "positions": int(np.prod(a.shape[:-1]))}
    if a.shape != b.shape:
        report.update(accepted=False,
                      reason=f"logits shape {b.shape} != {a.shape}")
        return report
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        report.update(accepted=False, reason="non-finite logits")
        return report
    agree = float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))
    spread = float(max(a.std(), 1e-6))
    max_abs = float(np.abs(a - b).max())
    mean_abs = float(np.abs(a - b).mean())
    report.update(greedy_agree=round(agree, 4),
                  max_abs=round(max_abs, 6),
                  mean_abs=round(mean_abs, 6),
                  ref_std=round(spread, 6),
                  max_rel=round(max_abs / spread, 6))
    if agree < min_agree:
        report.update(accepted=False,
                      reason=f"greedy argmax agreement {agree:.4f} < "
                             f"{min_agree}")
        return report
    if max_abs / spread > rel_tol:
        report.update(accepted=False,
                      reason=f"max |logit err| {max_abs:.4f} is "
                             f"{max_abs / spread:.3f}x the reference "
                             f"spread (> {rel_tol})")
        return report
    report["accepted"] = True
    return report


def run_weight_ab(cfg: ModelConfig, params, qparams, *, batch: int = 8,
                  seq: int = 48, seed: int = 0,
                  min_agree: Optional[float] = None,
                  rel_tol: Optional[float] = None,
                  wp: Optional[WeightPlaneConfig] = None
                  ) -> Dict[str, Any]:
    """The logits/output A-B: teacher-forced forward of the SAME random
    token batch through the f32 params and the dequantized weight-plane
    params (numerically what the engine's in-graph qdot contracts
    against), judged by :func:`weight_ab_report`. Returns the report
    dict — never raises on rejection, so benches record a failing rung
    as data (the ``run_loss_ab`` convention)."""
    from hadoop_tpu.models.decoder import forward
    wp = wp or BITWISE_WEIGHTS
    if min_agree is None:
        min_agree = wp.guard_min_agree
    if rel_tol is None:
        rel_tol = wp.guard_rel_tol
    seq = min(seq, cfg.max_seq)
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq),
                                0, cfg.vocab_size, dtype=jnp.int32)
    logits_ref = forward(params, tokens, cfg)
    logits_q = forward(dequantize_params(qparams, cfg), tokens, cfg)
    report = weight_ab_report(np.asarray(logits_ref, np.float32),
                              np.asarray(logits_q, np.float32),
                              min_agree=min_agree, rel_tol=rel_tol)
    report["batch"], report["seq"] = batch, seq
    return report


__all__ = [
    "WEIGHTS_PARITY_KEY", "TIERS", "LAYER_MATMULS", "EXPERT_STACKS",
    "WeightPlaneConfig", "BITWISE_WEIGHTS", "weightplane_from_conf",
    "quantize_weight", "dequantize_weight", "is_qtensor",
    "is_quantized_tree", "resident_weight_bytes", "describe_tree",
    "quantize_params", "make_load_quantizer", "quantized_load",
    "dequantize_params", "qdot", "qrows", "qhead", "qslice", "qedot",
    "expert_weight_bytes", "expert_shard_count",
    "weight_ab_report", "run_weight_ab",
]
