"""Open-loop load from one process of its own: each request is sent at
its due time whatever became of the others, is timed from when it was
*due*, and streams its tokens back through the router. How late the
sender ran is recorded for every request.

The window's load runs as ``python -m benchmark.loadgen`` beside the
harness, not inside it: the harness hosts the plane (router, scraper,
reconcile loops), and a sender that shares that interpreter's lock was
seen to run seconds late (PERF.md, PR 24)."""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional


def scrape(metrics_url: str, prefix: str = "kfx_lm_") -> Dict[str, float]:
    """Totals of the families that start with ``prefix`` on a replica's
    /metrics, summed over label sets."""
    with urllib.request.urlopen(metrics_url, timeout=30) as r:
        text = r.read().decode()
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith(prefix) and " " in line:
            name = line.split("{", 1)[0].split(" ", 1)[0]
            out[name] = out.get(name, 0.0) + float(line.rsplit(" ", 1)[1])
    return out


def stream_one(url: str, model: str, req: Dict[str, Any],
               timeout_s: float = 120.0) -> Dict[str, Any]:
    """Send one streamed :generate and read it to its end. Times are
    ``time.monotonic()`` at this client."""
    body = {"prompt_tokens": [req["prompt"]], "stream": True,
            "max_new_tokens": req["max_new_tokens"],
            "temperature": req.get("temperature", 0.0)}
    out: Dict[str, Any] = {"tokens": [], "times": [], "t_first": None,
                           "t_last": None,
                           "done": False, "timing": None, "error": None,
                           "t_sent": time.monotonic()}
    http = urllib.request.Request(
        f"{url}/v1/models/{model}:generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(http, timeout=timeout_s) as r:
            for raw in r:
                line = raw.decode().strip()
                if line.startswith("event: error"):
                    out["error"] = "stream error frame"
                if not line.startswith("data:"):
                    continue
                event = json.loads(line[len("data:"):])
                now = time.monotonic()
                if "error" in event:
                    out["error"] = str(event["error"])
                elif event.get("done"):
                    out["done"] = True
                    out["timing"] = event.get("timing")
                elif "token" in event:
                    if out["t_first"] is None:
                        out["t_first"] = now
                    out["t_last"] = now
                    out["tokens"].append(int(event["token"]))
                    out["times"].append(now)
    except (urllib.error.URLError, OSError, ValueError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    out["t_end"] = time.monotonic()
    return out


def open_loop(url: str, model: str, reqs: List[Dict[str, Any]],
              grace_s: float) -> Dict[str, Any]:
    """Send ``reqs`` (sorted by ``due_s``) on schedule; wait at most
    ``grace_s`` past the last due time for stragglers. Returns the
    window's start and per-request results (None where a request had
    not finished when the grace ran out)."""
    results: List[Optional[Dict[str, Any]]] = [None] * len(reqs)
    threads: List[threading.Thread] = []
    t0 = time.monotonic()

    def fire(i: int) -> None:
        results[i] = stream_one(url, model, reqs[i])

    for i, req in enumerate(reqs):
        wait = t0 + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=fire, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    limit = t0 + reqs[-1]["due_s"] + grace_s
    for t in threads:
        t.join(max(0.0, limit - time.monotonic()))
    return {"t0": t0, "results": list(results)}


def send_all(url: str, model: str, reqs: List[Dict[str, Any]],
             timeout_s: float = 600.0) -> List[Dict[str, Any]]:
    """Set-up traffic: send every request at once and wait for all."""
    out: List[Optional[Dict[str, Any]]] = [None] * len(reqs)

    def fire(i: int) -> None:
        out[i] = stream_one(url, model, reqs[i], timeout_s)

    threads = [threading.Thread(target=fire, args=(i,), daemon=True)
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout_s)
    bad = [i for i, r in enumerate(out)
           if r is None or r["error"] or not r["done"]]
    if bad:
        raise RuntimeError(f"set-up request(s) {bad} failed: "
                           f"{[out[i] and out[i]['error'] for i in bad]}")
    return out  # type: ignore[return-value]


def main(argv=None) -> int:
    """One window: make the mix's requests from the seed, send them on
    schedule, write what the client saw (times relative to the window's
    start, whose wall-clock time is ``t0_wall``) to ``--out``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--traffic", required=True, help="the mix's file")
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--grace", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from benchmark import manifest, traffic

    reqs = traffic.serve_requests(manifest.table(args.traffic), args.vocab,
                                  args.rate, args.seconds, args.seed)
    offset = time.time() - time.monotonic()
    res = open_loop(args.url, args.model, reqs, args.grace)
    t0 = res["t0"]
    rel = lambda t: None if t is None else t - t0
    out = {"t0_wall": t0 + offset, "results": [
        None if r is None else dict(
            r, times=[t - t0 for t in r["times"]], t_sent=rel(r["t_sent"]),
            t_first=rel(r["t_first"]), t_last=rel(r["t_last"]),
            t_end=rel(r["t_end"])) for r in res["results"]]}
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
