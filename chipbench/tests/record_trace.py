"""How ``trace_small.json`` was made: a few steps of a small jitted loop
under the profiler on the chip, the events cut to the first ``KEEP`` of
each plane. Run on the machine with the chip:

    python3 chipbench/tests/record_trace.py chiprun_out/trace_small.json
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
KEEP = 400


def main(path: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import trace

    @jax.jit
    def step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, None
        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(6):
        x = step(x)
        np.asarray(x[0, 0])         # a host sync, so there are idle gaps
    jax.profiler.stop_trace()
    events = trace.load_events(d)
    kept, seen = [], {}
    for e in sorted(events, key=lambda e: e["start_ns"]):
        seen[e["plane"]] = seen.get(e["plane"], 0) + 1
        if seen[e["plane"]] <= KEEP:
            kept.append(e)
    planes = sorted({(e["plane"], e["line"]) for e in events})
    with open(path, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "lines_seen": planes, "events": kept}, f)
    print(json.dumps(trace.reduce(kept))[:2000])


if __name__ == "__main__":
    main(sys.argv[1])
