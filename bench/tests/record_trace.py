"""How bench/tests/data/small_tpu.xplane.pb was made (PR 23, on the chip):

    chiprun -- python3 bench/tests/record_trace.py chiprun_out/bench_trace

A tiny traced window in the harness's own shape: a `bench.window` span
holding three `bench.search` spans, each a jitted sort inside a while_loop
(so the device line nests operations) followed by a fetch, with a host sleep
of 4 ms around each search (so the window has idle gaps to name, and the
millisecond by which the device's clock and the host's disagree in one trace
leaves every operation inside the window)."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax


def main(out_dir: str) -> int:
    assert jax.devices()[0].platform == "tpu", jax.devices()

    @jax.jit
    def search(x):
        def body(c):
            i, v = c
            return i + 1, jnp.sort(v * 3 + i) % 1009
        return lax.while_loop(lambda c: c[0] < 8, body, (0, x))[1].sum()

    x = jnp.arange(1 << 18, dtype=jnp.int32)
    search(x).block_until_ready()
    opt = jax.profiler.ProfileOptions()
    opt.python_tracer_level = 0
    opt.host_tracer_level = 2
    tmp = os.path.join(out_dir, "raw")
    jax.profiler.start_trace(tmp, profiler_options=opt)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.004)   # device and host clocks agree to ~1 ms only
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.search"):
                int(search(x))
            time.sleep(0.004)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out_dir, "small_tpu.xplane.pb"))
    shutil.rmtree(tmp)
    print("recorded", os.path.getsize(
        os.path.join(out_dir, "small_tpu.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
