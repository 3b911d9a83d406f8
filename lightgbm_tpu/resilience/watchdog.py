"""Budgeted backend watchdog: classify a jax backend as live/wedged/error
BEFORE committing this process to it.

A wedged accelerator plugin hangs *inside* backend init or the first
dispatch — no in-process timeout can recover from it (the GIL-holding C++
call never returns).  The only robust probe is a THROWAWAY SUBPROCESS with
a hard wall-clock budget: the child compiles and dispatches a tiny matmul
and prints one JSON line; the parent's verdict is

- ``live``   — the child printed its JSON within the budget,
- ``wedged`` — the child exceeded the budget (killed; backend unusable),
- ``error``  — the child exited nonzero (backend broken but not hung).

This module is deliberately runnable WITHOUT importing the lightgbm_tpu
package (stdlib-only at module level): importing the package pulls in
jax, and a wedged backend can hang even at import.  The fault seam
(wedge_dispatch) is re-implemented inline in the child source for the
same reason.

CLI::

    python lightgbm_tpu/resilience/watchdog.py [--timeout S] [--platform P]

exits 0 on live, 2 on wedged, 1 on error, printing the verdict JSON.
(Invoke by file path when the backend may be wedged: ``python -m``
imports the package __init__ — and therefore jax — in the parent.
``python -m lightgbm_tpu.resilience.watchdog`` works too, on a healthy
interpreter.)
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

DEFAULT_TIMEOUT_ENV = "LIGHTGBM_TPU_PROBE_TIMEOUT"
DEFAULT_TIMEOUT_S = 60.0

# The probe child: fault seam first (a simulated wedge must stall the probe
# exactly where a real one would — before any result escapes), then backend
# init + compile + dispatch, then ONE JSON line.
_PROBE_CHILD_SRC = r"""
import json, os, sys, time
t0 = time.time()
for part in os.environ.get("LIGHTGBM_TPU_FAULTS", "").split(","):
    name, _, val = part.partition(":")
    if name.strip() == "wedge_dispatch":
        time.sleep(float(val) if val.strip() else 3600.0)
import jax
import jax.numpy as jnp
x = jnp.ones((128, 128), jnp.float32)
(x @ x).block_until_ready()
print(json.dumps({
    "backend": jax.default_backend(),
    "devices": len(jax.devices()),
    "compile_dispatch_s": round(time.time() - t0, 3),
}))
"""


@dataclasses.dataclass
class ProbeResult:
    """One backend probe verdict."""

    verdict: str                    # "live" | "wedged" | "error"
    backend: Optional[str] = None
    devices: int = 0
    latency_s: float = 0.0
    budget_s: float = 0.0
    error: Optional[str] = None

    @property
    def live(self) -> bool:
        return self.verdict == "live"

    def as_dict(self) -> Dict:
        return {
            "verdict": self.verdict,
            "backend": self.backend,
            "devices": self.devices,
            "latency_s": round(self.latency_s, 3),
            "budget_s": self.budget_s,
            "error": self.error,
        }


def default_timeout() -> float:
    return float(os.environ.get(DEFAULT_TIMEOUT_ENV, DEFAULT_TIMEOUT_S))


def probe_backend(timeout: Optional[float] = None,
                  platform: Optional[str] = None,
                  extra_env: Optional[Dict[str, str]] = None) -> ProbeResult:
    """Run the budgeted subprocess probe.  ``platform`` pins
    ``JAX_PLATFORMS`` in the child (e.g. ``"cpu"`` to vet the fallback);
    the parent never touches jax and therefore can never hang."""
    budget = default_timeout() if timeout is None else float(timeout)
    env = dict(os.environ)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    env.update(extra_env or {})
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_CHILD_SRC],
            capture_output=True, text=True, timeout=budget, env=env)
    except subprocess.TimeoutExpired:
        return ProbeResult(
            verdict="wedged", latency_s=time.time() - t0, budget_s=budget,
            error=f"probe child exceeded its {budget:g}s budget "
                  "(backend init or dispatch hung)")
    elapsed = time.time() - t0
    line = None
    for ln in (proc.stdout or "").splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                line = json.loads(ln)
            except ValueError:
                pass
    if proc.returncode != 0 or line is None:
        tail = ((proc.stderr or "") + (proc.stdout or ""))[-400:]
        return ProbeResult(
            verdict="error", latency_s=elapsed, budget_s=budget,
            error=f"probe child rc={proc.returncode}: {tail}")
    return ProbeResult(
        verdict="live", backend=line.get("backend"),
        devices=int(line.get("devices", 0)), latency_s=elapsed,
        budget_s=budget)


# ------------------------------------------------------- engine preflight
WATCHDOG_ENV = "LIGHTGBM_TPU_WATCHDOG"


class BackendWedgedError(RuntimeError):
    """The budgeted probe classified the backend as wedged — raised instead
    of letting training hang inside backend init."""


def _backend_initialised() -> bool:
    """Has THIS process already initialised a jax backend?  (Never imports
    jax itself: a process that has not imported it has not.)"""
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(xb is not None and xb.backends_are_initialized())


def preflight(params: Optional[Dict] = None) -> Optional[ProbeResult]:
    """Opt-in training preflight (``LIGHTGBM_TPU_WATCHDOG=1``): probe the
    backend under the ``tpu_probe_timeout`` budget BEFORE the trainer's
    first device touch.  Wedged -> :class:`BackendWedgedError` (a clear
    crash beats an indefinite hang); error -> warn and continue (the
    in-process init will surface the real exception).

    A chip belongs to one process: once this process has initialised its
    backend it holds the chip, the probe child could not take it, and its
    failure would read as "wedged".  So the probe is refused then — the
    live in-process backend is the better evidence anyway.
    Returns the probe result, or None when the watchdog is not armed or
    the probe was refused."""
    if os.environ.get(WATCHDOG_ENV, "0") in ("", "0"):
        return None
    if _backend_initialised():
        _say("backend watchdog: this process already initialised its jax "
             "backend and holds the device; not probing from a second "
             "process (arm the watchdog before the first jax call)")
        return None
    params = params or {}
    budget = float(params.get("tpu_probe_timeout", default_timeout()) or
                   default_timeout())
    res = probe_backend(timeout=budget)
    try:
        # unified telemetry (docs/OBSERVABILITY.md): the probe verdict is
        # a registry gauge + a JSONL event.  Lazy and optional — this
        # module stays importable standalone (no package parent).
        from .. import telemetry
        telemetry.registry().counter(f"watchdog.{res.verdict}").inc()
        telemetry.registry().gauge("watchdog.probe_latency_s").set(
            res.latency_s)
        telemetry.emit("watchdog.probe", **res.as_dict())
    except ImportError:
        pass
    if res.verdict == "wedged":
        raise BackendWedgedError(
            f"backend watchdog: probe exceeded its {budget:g}s budget — the "
            "accelerator plugin is wedged; not starting training (run "
            "python -m lightgbm_tpu.resilience.watchdog to re-check, or "
            "set JAX_PLATFORMS=cpu for the CPU fallback)")
    if res.verdict == "error":
        _say(f"backend watchdog probe errored: {res.error}")
    return res


def _say(msg: str) -> None:
    try:
        from ..utils.log import Log
        Log.warning(msg)
    except ImportError:      # loaded standalone (no package parent)
        sys.stderr.write(f"[watchdog] {msg}\n")


# --------------------------------------------- multiprocess capability probe
@dataclasses.dataclass
class MPProbeResult:
    ok: bool
    reason: str = ""
    latency_s: float = 0.0


_MP_CHILD_SRC = r"""
import sys
pid, world, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
import jax
jax.distributed.initialize(coordinator_address=coord, num_processes=world,
                           process_id=pid)
import jax.numpy as jnp
from jax.experimental import multihost_utils
out = multihost_utils.process_allgather(jnp.full((2,), pid, jnp.int32))
assert out.reshape(-1).shape[0] == 2 * world, out.shape
print("MP_PROBE_OK")
"""

_mp_cache: Dict[int, MPProbeResult] = {}


def probe_multiprocess(num_processes: int = 2,
                       timeout: float = 120.0) -> MPProbeResult:
    """Can THIS jaxlib run collectives across real OS processes on the
    active backend?  (CPU jaxlib raises "Multiprocess computations aren't
    implemented on the CPU backend" — a known platform gap, not a
    regression.)  Spawns ``num_processes`` children that bootstrap
    ``jax.distributed`` over loopback and cross-process allgather; the
    verdict is cached per process so test collection pays it once."""
    cached = _mp_cache.get(num_processes)
    if cached is not None:
        return cached
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    t0 = time.time()
    procs: List[subprocess.Popen] = []
    try:
        for pid in range(num_processes):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _MP_CHILD_SRC,
                 str(pid), str(num_processes), coord],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        res = MPProbeResult(False, f"probe hung past {timeout:g}s",
                            time.time() - t0)
        _mp_cache[num_processes] = res
        return res
    bad = [(rc, err) for rc, out, err in outs
           if rc != 0 or "MP_PROBE_OK" not in out]
    if bad:
        reason = (bad[0][1] or "").strip().splitlines()
        res = MPProbeResult(False, reason[-1][-200:] if reason else
                            f"probe child rc={bad[0][0]}", time.time() - t0)
    else:
        res = MPProbeResult(True, "", time.time() - t0)
    _mp_cache[num_processes] = res
    return res


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="budgeted jax backend probe: live/wedged/error")
    ap.add_argument("--timeout", type=float, default=None,
                    help=f"budget seconds (default ${DEFAULT_TIMEOUT_ENV} "
                         f"or {DEFAULT_TIMEOUT_S:g})")
    ap.add_argument("--platform", default=None,
                    help="pin JAX_PLATFORMS in the probe child")
    args = ap.parse_args(argv)
    res = probe_backend(timeout=args.timeout, platform=args.platform)
    print(json.dumps(res.as_dict()))
    return {"live": 0, "wedged": 2}.get(res.verdict, 1)


if __name__ == "__main__":
    sys.exit(main())
