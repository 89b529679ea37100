"""How bench/tests/data/small_tpu_scoped.xplane.pb was made (PR 24, on the
chip):

    chiprun -- python3 bench/tests/record_trace_scoped.py chiprun_out/bench_trace

`record_trace.py`'s window with the program's two kinds of names in it, as
`jaxmc/` writes them: the jitted while-loop's body runs under two
`jax.named_scope`s (`jaxmc.merge.sort` round the sort, `jaxmc.expand` round
the arithmetic before it) with one reduction outside any scope, and each
`bench.search` holds two `jaxmc.*` TraceAnnotations: `jaxmc.search.seed`
over a host sleep with the device idle, `jaxmc.search.dispatch` over the
call and its fetch.  A last sleep inside the search lies under no `jaxmc.*`
span.  So the trace has scoped and unscoped device time, and attributed and
unattributed idle time, for bench/spans.py to tell apart."""

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
            with jax.named_scope("jaxmc.expand"):
                w = v * 3 + i
            with jax.named_scope("jaxmc.merge.sort"):
                w = jnp.sort(w)
            return i + 1, w % 1009
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
                with jax.profiler.TraceAnnotation("jaxmc.search.seed"):
                    time.sleep(0.003)
                with jax.profiler.TraceAnnotation("jaxmc.search.dispatch"):
                    int(search(x))
                time.sleep(0.002)
            time.sleep(0.004)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out_dir, "small_tpu_scoped.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print("recorded", os.path.getsize(dst), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
