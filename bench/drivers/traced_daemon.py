"""`python3 bench/drivers/traced_daemon.py run --workers 1 --spool <dir>`:
the jaxmc.serve daemon, started so that the benchmark can trace the chip.

Only the process that holds the chip can trace it, and on the served path
that is the daemon's device-owner CHILD, which the daemon spawns
(multiprocessing, spawn context).  A spawned child imports its parent's
main module again, so the lines at module level below run in the owner too
and start one thread there.  The thread waits for the benchmark's client to
create `start` in BENCH_OWNER_TRACE_DIR, switches `jax.profiler` on if THIS
process already holds a live backend (the daemon itself never does, and the
thread never creates one), keeps a `bench.window` span open until `stop`
appears, and switches it off.  Nothing of the program is changed or
patched; used with `--trace 1` only.
"""

import os
import sys
import threading
import time


def _has_backend() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return bool(xla_bridge.backends_are_initialized())


def _hook(trace_dir: str) -> None:
    flag = lambda name: os.path.join(trace_dir, name)  # noqa: E731
    while not os.path.exists(flag("start")):
        time.sleep(0.01)
    if not _has_backend():
        return
    import jax
    opt = jax.profiler.ProfileOptions()
    opt.python_tracer_level = 0
    opt.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opt)
    with jax.profiler.TraceAnnotation("bench.window"):
        open(flag("started"), "w").close()
        while not os.path.exists(flag("stop")):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    open(flag("stopped"), "w").close()


if os.environ.get("BENCH_OWNER_TRACE_DIR"):
    threading.Thread(target=_hook, name="bench-trace-hook", daemon=True,
                     args=(os.environ["BENCH_OWNER_TRACE_DIR"],)).start()

if __name__ == "__main__":
    from jaxmc.serve.__main__ import main
    sys.exit(main(sys.argv[1:]))
