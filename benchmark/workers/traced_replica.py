"""A serving replica that traces itself: the program's model server,
unchanged, plus one thread that takes a profiler trace when asked.

Only the process that holds the chip can trace it, and the replica is
program code. The InferenceService of a ``--trace 1`` run names this
file as a custom container; everything but ``--trace-dir`` is handed
to ``kubeflow_tpu.serving.server.main`` as the operator would have
passed it. The thread waits for ``<trace-dir>/trace.request``
({"after_s", "seconds"}), traces that many seconds into
``<trace-dir>/trace``, and writes ``trace.done`` with the device's
memory statistics."""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time


def tracer(trace_dir: str) -> None:
    request = os.path.join(trace_dir, "trace.request")
    while not os.path.exists(request):
        time.sleep(0.05)
    time.sleep(0.05)  # the writer closes the file
    with open(request) as f:
        req = json.load(f)
    time.sleep(req["after_s"])
    import jax

    t0 = time.time()
    jax.profiler.start_trace(os.path.join(trace_dir, "trace"))
    time.sleep(req["seconds"])
    jax.profiler.stop_trace()
    done = {"t_start": t0, "t_stop": time.time(),
            "memory_stats": jax.local_devices()[0].memory_stats() or {}}
    tmp = os.path.join(trace_dir, "trace.done.tmp")
    with open(tmp, "w") as f:
        json.dump(done, f)
    os.replace(tmp, os.path.join(trace_dir, "trace.done"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", required=True)
    args, server_argv = ap.parse_known_args(argv)
    threading.Thread(target=tracer, args=(args.trace_dir,), daemon=True,
                     name="bench-tracer").start()
    from kubeflow_tpu.serving.server import main as serve

    return serve(server_argv)


if __name__ == "__main__":
    sys.exit(main())
