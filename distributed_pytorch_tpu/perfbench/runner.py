"""Benchmark execution: one backend probe, JSON subprocesses.

The plumbing ``bench.py`` and the mfu sweep share:

* **a probe in a child** (:func:`probe_backend`) — a chip belongs to one
  process at a time, so a parent that goes on to spawn measurement
  children must not initialize a JAX backend itself; the child takes the
  chip, reports what it found and releases it. Only a real TPU counts
  (a CPU fallback would grind the flagship through interpret-mode pallas
  for hours);
* **parseable-record-no-matter-what** (:func:`run_json_subprocess`) —
  on any child failure (nonzero exit, timeout, unparseable output) the
  caller still gets an ``error`` record carrying whatever the child did
  produce, so a record is *always* emitted with provenance instead of
  nothing;
* the ``#``-prefixed flushed progress contract (:func:`progress`,
  :func:`arm`) that keeps per-arm attribution in a killed child's
  stdout tail.

Module level is stdlib-only; the typed env registry is imported lazily
(same standalone-load contract as the rest of ``perfbench``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Callable, Optional

__all__ = ["REPO", "probe_backend", "progress", "arm",
           "run_json_subprocess"]

#: Repo root (three levels up: perfbench/ -> distributed_pytorch_tpu/ ->
#: repo) — the PYTHONPATH every benchmark child needs on sys.path.
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _env():
    from ..runtime import env
    return env


def probe_backend(timeout_s: int = 120) -> dict:
    """What JAX finds, asked in a SUBPROCESS so that this process stays
    off the chip for the children it is about to start. Returns
    ``{"platform": "tpu", "kind": ...}`` for a TPU and ``{}`` otherwise,
    with the reason on stderr."""
    code = ("import jax, json; d = jax.devices()[0]; "
            "print(json.dumps({'platform': d.platform, "
            "'kind': d.device_kind}))")
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"# backend probe: no answer in {timeout_s}s",
              file=sys.stderr)
        return {}
    try:
        info = json.loads(out.stdout.strip().splitlines()[-1])
    except (IndexError, json.JSONDecodeError):
        print(f"# backend probe: rc={out.returncode} "
              f"{out.stderr.strip()[-300:]}", file=sys.stderr)
        return {}
    if info.get("platform") != "tpu":
        print(f"# backend probe: JAX found {info}, not a TPU",
              file=sys.stderr)
        return {}
    return info


def progress(msg: str) -> None:
    """One flushed "#"-prefixed stdout line — the progress contract every
    on-chip stage leans on: "#" preserves the parse-last-line-as-JSON
    collector contract, and the flush makes the line survive a collector
    SIGKILL (block-buffered pipes lose unflushed output), so a stage
    killed at its timeout shows in its kept stdout tail how far it got."""
    print(f"# {msg}", flush=True)


def arm(label: str, thunk: Callable):
    """Banner-then-run: announce ``label`` via :func:`progress`, then
    execute the zero-arg ``thunk`` and return its result.  The one
    shared shape for multi-arm benchmark stages — the banner prints
    BEFORE any of the arm's work (setup included), so a hang anywhere
    in the arm is attributed to the right label in the kept stdout
    tail."""
    progress(label)
    return thunk()


def run_json_subprocess(argv, timeout_s: int, *, label: str,
                        env: Optional[dict] = None,
                        keep_stdout_tail: bool = False) -> dict:
    """Run a subprocess with a hard timeout and parse its LAST stdout
    line as JSON.  Single implementation of the
    parseable-record-no-matter-what contract — used by bench.py's stage
    runner and dp8 bench, and the mfu sweep.
    On any failure (nonzero exit, timeout, unparseable output) returns
    an ``error`` record carrying whatever the child did produce — a
    stage that prints its record and then exits nonzero (e.g. a failed
    numerics validation) keeps its measurements, marked with ``error``
    and ``rc``.  ``keep_stdout_tail`` preserves the human-readable tail
    (tables) alongside the parsed record."""
    _e = _env()
    base_env = _e.environ_copy()
    base_env["PYTHONPATH"] = (REPO + os.pathsep
                              + (_e.raw("PYTHONPATH") or ""))
    if env:
        base_env.update(env)
    try:
        out = subprocess.run(argv, capture_output=True, text=True,
                             timeout=timeout_s, env=base_env)
    except subprocess.TimeoutExpired as e:
        # TimeoutExpired carries the partial output (text decoded when
        # the child wrote any) — keep it: the progress lines before the
        # hang are exactly the diagnostics needed
        rec = {"error": f"{label} timed out after {timeout_s}s"}
        # stdout gets a wider tail than stderr: sweep stages emit one
        # "# ..." progress line per completed arm to stdout precisely so
        # a timeout keeps the partial per-arm record
        for name, cap in (("stdout", 2500), ("stderr", 800)):
            v = getattr(e, name, None)
            if v:
                if isinstance(v, bytes):
                    v = v.decode(errors="replace")
                rec[f"{name}_tail"] = v.strip()[-cap:]
        return rec

    payload = None
    if out.stdout.strip():
        try:
            payload = json.loads(out.stdout.strip().splitlines()[-1])
        except json.JSONDecodeError:
            payload = None
    if isinstance(payload, dict):
        if out.returncode != 0:
            payload.setdefault(
                "error", f"{label} exited rc={out.returncode}")
            payload["rc"] = out.returncode
    elif out.returncode == 0 and payload is not None:
        payload = {"value": payload}
    else:
        payload = {"error": (out.stderr or "no parseable output")
                   .strip()[-500:] or f"{label} produced no output"}
    if keep_stdout_tail:
        payload["stdout_tail"] = out.stdout.strip()[-1500:]
    return payload
