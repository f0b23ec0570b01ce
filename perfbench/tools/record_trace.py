#!/usr/bin/env python3
"""Record the small trace the reducer's test reads
(``tests/perfbench/data/*.xplane.pb``): a few steps of a tiny jitted
program under the same profiler options and annotations as a traced run,
with a pause between two of the steps so that there is an idle gap to name.
On several chips the program also sums over the mesh, so that the trace
holds a collective.

    chiprun -- python3 perfbench/tools/record_trace.py [out_dir]
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_fixture")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from perfbench import xplane

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("data",))
    x = jax.device_put(jnp.ones((8 * len(devs), 512), jnp.bfloat16),
                       NamedSharding(mesh, P("data")))
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        y = jnp.tanh(x @ w)
        return x + y * 1e-3, jnp.sum(y.astype(jnp.float32))

    x, s = step(x, w)
    float(s)
    tmp = os.path.join(out_dir, "tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    t0 = time.monotonic()
    for i in range(4):
        with jax.profiler.TraceAnnotation("perfbench.train_step"):
            x, s = step(x, w)
            float(s)
        if i == 1:
            with jax.profiler.TraceAnnotation("perfbench.pause"):
                time.sleep(0.02)
    window = time.monotonic() - t0
    jax.profiler.stop_trace()
    path = xplane.newest_xplane(tmp)
    name = f"tiny_{devs[0].platform}_{len(devs)}.xplane.pb"
    shutil.copy(path, os.path.join(out_dir, name))
    shutil.rmtree(tmp, ignore_errors=True)
    red = xplane.reduce(os.path.join(out_dir, name))
    red["window_s"] = window
    with open(os.path.join(out_dir, name + ".json"), "w") as fh:
        json.dump(red, fh, indent=1)
    print(json.dumps({"file": name, "bytes": os.path.getsize(
        os.path.join(out_dir, name)), **red})[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
