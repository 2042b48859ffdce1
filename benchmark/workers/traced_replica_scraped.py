"""``benchmark/workers/traced_replica.py`` with one thing more: the
replica scrapes its own ``/metrics`` at the two edges of the traced
seconds, so that a metric can take its counts from the very steps
whose device time the trace holds (the window's own two scrapes lie a
window and a drain apart, where fewer rows are live).

The same protocol: the thread waits for ``<trace-dir>/trace.request``
({"after_s", "seconds"}), traces that many seconds into
``<trace-dir>/trace``, and writes ``trace.done`` with the device's
memory statistics and ``counters`` ({"before", "after"}: the
``kfx_lm_`` families' totals, scraped once the profiler runs and
again before it stops). Everything but ``--trace-dir`` is handed to
``kubeflow_tpu.serving.server.main`` as the operator would have passed
it; the port is read off those arguments."""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

from benchmark import loadgen


def tracer(trace_dir: str, metrics_url: str) -> None:
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
    before = loadgen.scrape(metrics_url)
    time.sleep(req["seconds"])
    after = loadgen.scrape(metrics_url)
    jax.profiler.stop_trace()
    done = {"t_start": t0, "t_stop": time.time(),
            "counters": {"before": before, "after": after},
            "memory_stats": jax.local_devices()[0].memory_stats() or {}}
    tmp = os.path.join(trace_dir, "trace.done.tmp")
    with open(tmp, "w") as f:
        json.dump(done, f)
    os.replace(tmp, os.path.join(trace_dir, "trace.done"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", required=True)
    args, server_argv = ap.parse_known_args(argv)
    port = next(a.split("=", 1)[1] for a in server_argv
                if a.startswith("--port="))
    threading.Thread(
        target=tracer, daemon=True, name="bench-tracer",
        args=(args.trace_dir, f"http://127.0.0.1:{port}/metrics")).start()
    from kubeflow_tpu.serving.server import main as serve

    return serve(server_argv)


if __name__ == "__main__":
    sys.exit(main())
