#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests/test_trace.py`` reduces.

    python3 bench/record_trace.py <out_dir>

Five steps of a jitted bf16 matmul chain on one chip, each followed by 20 ms
of host sleep inside a ``bench/host_sleep`` span, all inside
``bench/traced_window``.  Writes ``<out_dir>/small.xplane.pb`` and prints the
planes and lines the profiler wrote.  Needs a TPU.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace.py needs a TPU")
    tmp = os.path.join(out_dir, "profile")
    shutil.rmtree(tmp, ignore_errors=True)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f = jax.jit(lambda x: jnp.tanh(x @ x @ x @ x))
    f(x).block_until_ready()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench/traced_window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench/step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/host_sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(out_dir, "small.xplane.pb"))
    for plane in pd.planes:
        lines = {line.name: sum(1 for _ in line.events) for line in plane.lines}
        print(json.dumps({"plane": plane.name, "lines": lines}))
    print(json.dumps({"bytes": os.path.getsize(os.path.join(out_dir, "small.xplane.pb")),
                      "kind": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
