"""What every cell's run shares: the plane in a scratch home inside the
checkout, finding and ending this run's children, reading their logs,
child processes of the benchmark's own (export writer, reference,
trace reduction), and the one result line.

This process never imports jax: a chip has one owner, and the owner is
the worker or the replica the plane spawns, then the reference child.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from .manifest import ROOT

SCRATCH = os.path.join(ROOT, ".bench_scratch")
T0 = time.time()            # this process's start, the origin of setup_s
_M0 = time.monotonic()


class RunFailure(Exception):
    """The run cannot produce a result: no result line, exit code 1."""

    def __init__(self, message: str, log: str = ""):
        super().__init__(message)
        self.log = log


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _M0:7.1f}s] {msg}", flush=True)


def check(ok: bool, what: str, log: str = "") -> None:
    if not ok:
        raise RunFailure(what, log)


def fresh_dir(*parts: str) -> str:
    path = os.path.join(SCRATCH, *parts)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def read(path: str, offset: int = 0) -> str:
    if not os.path.exists(path):
        return ""
    with open(path, "rb") as f:
        f.seek(offset)
        return f.read().decode(errors="replace")


def size(path: str) -> int:
    return os.path.getsize(path) if os.path.exists(path) else 0


def tagged(log: str, tag: str) -> List[str]:
    return [line[len(tag):].strip() for line in log.splitlines()
            if line.startswith(tag)]


def compilations(log: str) -> int:
    """XLA compilations a child logged (``JAX_LOG_COMPILES=1``)."""
    return sum("Finished XLA compilation" in line
               for line in log.splitlines())


def children(home: str, needle: str = "") -> List[int]:
    """Live processes of this run, read from /proc: their command line
    or working directory names the run's home (chip_smoke's rule)."""
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{entry}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
            cwd = os.readlink(f"/proc/{entry}/cwd")
        except OSError:
            continue  # gone between listdir and open
        if state != "Z" and needle in cmd and (home in cmd or home in cwd):
            found.append(int(entry))
    return found


def wait_gone(home: str, what: str, seconds: float = 30.0) -> None:
    limit = time.monotonic() + seconds
    while children(home):
        check(time.monotonic() < limit,
              f"{what} still alive: pids {children(home)}")
        time.sleep(0.1)


def kill_children(home: str) -> None:
    for pid in children(home):
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def child_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env.pop("JAX_LOG_COMPILES", None)  # the plane's children only
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def run_child(module: str, args: List[str], log_path: str,
              env: Optional[Dict[str, str]] = None,
              timeout_s: float = 900.0) -> str:
    """Run ``python -m <module>`` of the benchmark's own to its end;
    its output goes to ``log_path`` and is returned."""
    with open(log_path, "wb") as logf:
        proc = subprocess.Popen([sys.executable, "-m", module] + args,
                                cwd=ROOT, env=child_env(env), stdout=logf,
                                stderr=subprocess.STDOUT)
        try:
            rc = proc.wait(timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise RunFailure(f"{module} ran over {timeout_s:.0f}s",
                             read(log_path)) from None
    log = read(log_path)
    check(rc == 0, f"{module} exited with code {rc}", log)
    return log


def child_result(log: str, tag: str = "result ") -> Dict[str, Any]:
    lines = tagged(log, tag)
    check(bool(lines), f"child printed no {tag.strip()!r} line", log)
    return json.loads(lines[-1])


def device_of(log: str, who: str, chips: int, require_tpu: bool
              ) -> Dict[str, Any]:
    """The device line a child printed about itself. A run on anything
    but a TPU with the chips the cell asks for has no result."""
    lines = tagged(log, "device ")
    check(bool(lines), f"{who} printed no device line", log)
    # raw_decode: another thread's log line may share the line's end
    dev, _ = json.JSONDecoder().raw_decode(lines[-1])
    if require_tpu:
        check(dev["platform"] == "tpu",
              f"no accelerator: {who} reports {dev}", log)
        check(dev["count"] >= chips,
              f"{who} holds {dev['count']} chip(s), the cell needs {chips}")
    return dev


def require_chips(cp, chips: int) -> None:
    """No result without the chips the cell asks for, on a plane that
    hands its workers an accelerator (kfx's scheduler counts the
    host's device nodes)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    check(platforms != "cpu" and cp.sched.capacity >= chips,
          f"no accelerator: this host exposes {cp.sched.capacity} chip(s) "
          f"to a plane with JAX_PLATFORMS={platforms!r}; the cell needs "
          f"{chips}")


def print_comparison(rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Each number compared beside its limit, into the log and onto
    standard error, where these are the run's last lines (the harness
    writes nothing else there); the rows come back with a ``held`` flag
    each. ``correct`` is all of them."""
    out = []
    for r in rows:
        held = bool(r["value"] <= r["limit"])
        out.append(dict(r, held=held))
        text = (f"compared {r['name']}: value={r['value']:.6g} "
                f"limit={r['limit']:.6g} {'ok' if held else 'OVER'}")
        say(text)
        print(text, file=sys.stderr, flush=True)
    return out


def result_line(compared: List[Dict[str, Any]], attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The run's one line. ``correct`` is true when every compared
    number held; the numbers stand beside their limits under
    ``compared``, the line's last key, so that a refused ``correct``
    says in the one line the driver keeps which number failed."""
    line: Dict[str, Any] = {"correct": all(r["held"] for r in compared),
                            "attempted": int(attempted),
                            "failed": int(failed), "metrics": metrics,
                            "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in compared}
    return json.dumps(line)
