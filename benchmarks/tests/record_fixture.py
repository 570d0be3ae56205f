"""How ``fixture_v5e.xplane.pb`` was recorded (on the chip, PR 22):

    chiprun -- python benchmarks/tests/record_fixture.py

Three calls of a small jitted program (a matmul under a named scope and a
sum) inside the harness's window annotation, with one idle sleep between
calls, so that the reduction has operations, gaps and a window to find.
The trace lands in ``chiprun_out/fixture_v5e.xplane.pb``; the test
(``test_trace_reduce.py``) pins what the reduction reads from it.
"""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import jax.profiler

    from benchmarks.trace.reduce import WINDOW_ANNOTATION
    if jax.devices()[0].platform != "tpu":
        print("record_fixture.py: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def program(x):
        with jax.named_scope("fixture_matmul"):
            y = x @ x
        return jnp.sum(y)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    program(x).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out")
    tmp = os.path.join(out, "fixture_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION,
                                      unix_ns=time.time_ns()):
        for _ in range(3):
            program(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(found[0], os.path.join(out, "fixture_v5e.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out, "fixture_v5e.xplane.pb")),
          "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
