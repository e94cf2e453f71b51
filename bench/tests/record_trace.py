"""Record the small chip trace ``test_profile_reduce.py`` reads.

    python3 bench/tests/record_trace.py      # on a machine with a TPU

Runs two small jitted programs three times between the benchmark's own
markers (``bench.window`` at both ends, ``bench.call.start`` and
``bench.call.end`` around each pair of launches, a host pause between
calls), writes the profiler's
``.xplane.pb`` to ``bench/tests/data/small.xplane.pb`` and prints the
planes and lines it holds, with the programs' device times the
reduction finds.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1])]


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench import profile_reduce
    from bench.cell import mark
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    mm = jax.jit(lambda a, b: (a @ b).astype(jnp.bfloat16))
    ew = jax.jit(lambda a: jnp.tanh(a) * 2 + 1)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((mm(a, b), ew(a)))
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    mark(profile_reduce.WINDOW)
    for _ in range(3):
        mark(profile_reduce.CALL_START)
        c = mm(a, b)
        time.sleep(0.002)
        jax.block_until_ready(ew(c))
        mark(profile_reduce.CALL_END)
        time.sleep(0.005)
    mark(profile_reduce.WINDOW)
    jax.profiler.stop_trace()
    path = next(Path(tmp).rglob("*.xplane.pb"))
    out = HERE / "data" / "small.xplane.pb"
    shutil.copy(path, out)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(out)).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name!r}: {lines}")
    print(profile_reduce.read(str(out)))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
