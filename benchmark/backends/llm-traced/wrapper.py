"""The in-tree llm backend with a profiler control thread beside it.

Only the process that holds the chip can trace it, and the program has no hook
for that, so the traced run starts the backend through LocalAI's external
backend contract (core/manager.py, services/backend_gallery.py) and this file
wraps `localai_tpu.backend.__main__.main`. No program file is edited and
nothing is patched: the thread below only calls jax.profiler.

Protocol, by files in the directory BENCH_TRACE_CTL names:
  start    (harness)  -> start_trace into BENCH_TRACE_DIR, then write `started`
  stop     (harness)  -> stop_trace, then write `done`
  error    (wrapper)  a traceback, instead of `started` or `done`
`started` and `done` hold the unix time in ns at which the call returned.
The cycle can run again after the harness has removed those files.
"""
import json
import os
import sys
import threading
import time
import traceback


def _write(ctl, name, payload):
    tmp = os.path.join(ctl, name + ".tmp")
    with open(tmp, "w") as f:
        f.write(payload)
    os.replace(tmp, os.path.join(ctl, name))


def _wait_for(ctl, name):
    path = os.path.join(ctl, name)
    while not os.path.exists(path):
        time.sleep(0.02)
    os.remove(path)


def control(ctl, trace_dir):
    while True:
        _wait_for(ctl, "start")
        try:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no Python frames
            opts.host_tracer_level = 1        # the lowest that keeps TraceMe
            t0 = time.time_ns()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            _write(ctl, "started", json.dumps(
                {"called_ns": t0, "returned_ns": time.time_ns()}))
            _wait_for(ctl, "stop")
            t1 = time.time_ns()
            jax.profiler.stop_trace()
            _write(ctl, "done", json.dumps(
                {"called_ns": t1, "returned_ns": time.time_ns()}))
        except Exception:
            _write(ctl, "error", traceback.format_exc())
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass


def main():
    ctl = os.environ.get("BENCH_TRACE_CTL")
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if ctl and trace_dir:
        threading.Thread(target=control, args=(ctl, trace_dir),
                         name="bench-trace", daemon=True).start()
    from localai_tpu.backend.__main__ import main as backend_main

    return backend_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
