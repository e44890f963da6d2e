"""Record the small profiler trace that the trace reduction's test reads.

    python3 benchmarks/chip/record_trace.py <out.json>

On the chip: a window span holding two ``bench.execute_step`` spans, each
around a few jitted matmuls, with host sleeps between them so that the
device idles; writes what ``trace.load`` keeps (device operations and
benchmark spans) as JSON.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import harness, trace  # noqa: E402


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    harness.require_tpu(1)
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(2):
            time.sleep(0.02)
            with TraceAnnotation("bench.execute_step"):
                for _ in range(3):
                    x = f(x)
                x.block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    raw = trace.load(d)
    with open(out, "w") as fh:
        json.dump(raw, fh)
    print(json.dumps(trace.reduce(raw)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
