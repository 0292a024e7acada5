"""Record the small chip trace that ``test_trace.py`` reads.

    python3 chipbench/tests/record_trace.py <out_dir>

Run on the chip. Traces a short loop of a jitted matmul under the
harness's span names (``window``, ``feed.next``, ``step``, with a host
sleep between steps), copies the ``.xplane.pb`` to
``<out_dir>/small.xplane.pb`` and prints each plane's lines and event
counts, then the reduction of the trace.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path[0] = root
    from chipbench import trace

    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("feed.next"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("step"):
                for _ in range(4):
                    x = f(x)
                x.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(out_dir, "small.xplane.pb"))
    for plane in pd.planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        print(json.dumps({"plane": plane.name, "lines": lines}))
    print(json.dumps(trace.reduce_profile(pd, n_devices=1)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
