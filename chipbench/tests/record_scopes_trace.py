"""How ``scopes_small.json`` was made: a few steps of the program's own
train step and of the engine's fused step, both two layers deep at small
widths, under the profiler on the chip; the device events of the "XLA
Modules" and "XLA Ops" lines with the path the reduction reads, the first
``RUNS`` runs of each compiled program. Run on the machine with the chip:

    python3 chipbench/tests/record_scopes_trace.py chiprun_out/scopes_small.json

With a second argument it also writes, for a look by hand, every line of
every plane with its first events and ALL their stats, and beside it the
raw ``.xplane.pb``.
"""

import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
RUNS = 2           # of each compiled program
NAME_KEEP = 96     # an operation's name is its whole HLO line


def _programs():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hadoop_tpu.models.config import ModelConfig
    from hadoop_tpu.models.decoder import init_params
    from hadoop_tpu.parallel.mesh import MeshPlan, make_mesh
    from hadoop_tpu.parallel.optimizer import adamw_init
    from hadoop_tpu.parallel.train import make_train_step
    from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams

    cfg = ModelConfig(family="llama", vocab_size=2048, d_model=256,
                      n_layers=2, n_heads=2, n_kv_heads=1, d_ff=512,
                      max_seq=512, dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = MeshPlan()
    step = make_train_step(cfg, plan, make_mesh(plan, jax.devices()[:1]),
                           remat="dots")
    opt = adamw_init(params)
    tok = jnp.asarray(np.arange(512, dtype=np.int32)[None] % 2048)
    train = {"params": jax.tree_util.tree_map(jnp.copy, params),
             "opt": opt}

    def train_once():
        train["params"], train["opt"], m = step(train["params"],
                                                train["opt"], tok, tok)
        return float(m["loss"])

    eng = DecodeEngine(params, cfg, max_batch=4, block_size=16,
                       max_context=256, prefill_chunk=16)

    def serve_once():
        eng.generate([list(range(1, 40)), [5, 6, 7]],
                     SamplingParams(max_new_tokens=6))

    return train_once, serve_once


def cut(events):
    """Whole runs only, so that a program's operations add up to it: the
    first ``RUNS`` runs of each compiled program and the operations in
    them, names cut to ``NAME_KEEP``."""
    from chipbench import scopes
    runs, taken = [], {}
    for e in sorted(events, key=lambda e: e["start_ns"]):
        if e["line"] == scopes.MODULES_LINE:
            name = scopes.module_name(e["name"])
            taken[name] = taken.get(name, 0) + 1
            if taken[name] <= RUNS:
                runs.append(e)
    kept = list(runs)
    for e in events:
        if e["line"] != scopes.MODULES_LINE and any(
                r["start_ns"] <= e["start_ns"] < r["start_ns"] + r["dur_ns"]
                for r in runs):
            kept.append(dict(e, name=e["name"][:NAME_KEEP]))
    return kept


def main(path: str, look: str = None) -> None:
    import jax
    from jax.profiler import ProfileData

    train_once, serve_once = _programs()
    train_once()
    serve_once()                    # both shapes compiled
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(2):
        train_once()
    serve_once()
    jax.profiler.stop_trace()
    pb, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(pb)

    from chipbench import scopes
    events = scopes.load_events(pb)
    kept = cut(events)
    with open(path, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "events": kept}, f)
    print(len(events), "events,", len(kept), "kept")
    print(json.dumps(scopes.reduce(kept))[:3000])

    if look:
        import shutil
        shutil.copy(pb, look + ".xplane.pb")
        out = []
        for plane in data.planes:
            for line in plane.lines:
                evs = list(line.events)
                out.append({
                    "plane": plane.name, "line": line.name, "n": len(evs),
                    "first": [{"name": ev.name[:400],
                               "start_ns": ev.start_ns,
                               "dur_ns": ev.duration_ns,
                               "stats": {k: str(v)[:400]
                                         for k, v in ev.stats}}
                              for ev in evs[:60]]})
        with open(look, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(*sys.argv[1:3])
