"""The system under test: `python -m ingress_plus_tpu.serve`, unchanged.

The server is the one process that holds the chip, so only it can say
how much device memory it used and only it can trace the device.  This
wrapper adds two things around the program's own entry point, and
changes nothing in it:

* an exit hook that prints the device's peak memory
  (`device.memory_stats()`), as `device_memory: {...}` on stderr;
* where `BENCH_TRACE_DIR` is set, a profiler trace of just the window:
  SIGUSR1 starts `jax.profiler` into that directory (Python tracer off:
  with it on, the host slows until the server sheds), SIGUSR2 stops it.
  Each prints a `profiler: ...` line when done.  The program's own
  `--trace-dir` traces the whole serve loop with the Python tracer on
  and writes at shutdown; it is not used.
"""

import atexit
import json
import os
import runpy
import signal
import sys
import threading
import time


def _report_memory() -> None:
    jax = sys.modules.get("jax")
    if jax is None:
        return
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    known = [p for p in peaks if p is not None]
    print("device_memory: %s" % json.dumps(
        {"memory_peak_bytes": max(known) if known else None}),
        file=sys.stderr, flush=True)


def _profiler_switch(trace_dir: str) -> None:
    def start() -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        print("profiler: started", file=sys.stderr, flush=True)

    def stop() -> None:
        import jax

        t0 = time.monotonic()
        jax.profiler.stop_trace()
        print("profiler: stopped, written in %.1fs"
              % (time.monotonic() - t0), file=sys.stderr, flush=True)

    # the handlers run on the event loop's thread: hand the work over
    for sig, work in ((signal.SIGUSR1, start), (signal.SIGUSR2, stop)):
        signal.signal(sig, lambda _s, _f, work=work: threading.Thread(
            target=work, daemon=True).start())


if __name__ == "__main__":
    atexit.register(_report_memory)
    if os.environ.get("BENCH_TRACE_DIR"):
        _profiler_switch(os.environ["BENCH_TRACE_DIR"])
    sys.argv[0] = "ingress_plus_tpu.serve"
    runpy.run_module("ingress_plus_tpu.serve", run_name="__main__",
                     alter_sys=True)
