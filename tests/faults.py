"""Fault-injection chaos harness for the serving tier.

Used by the fault-tolerance tests (tests/test_serving_faults.py,
tests/test_pool_scaleout.py, tests/test_checkpoint.py and others). Three
layers:

  * :class:`FaultInjector` — deterministic failure schedules installed on
    the ``EnginePool`` fault points (launch.pool.FAULT_POINTS): fail the
    next N calls, fail forever, fail specific call indices, or fail with
    seeded probability — per point, optionally per stream — plus
    ``kill_host`` schedules that drop a scale-out host at an exact
    ``host_op`` call index (the machine-loss fault, tests/test_pool_
    scaleout.py);
  * corruption generators — :func:`corrupt_checkpoint` (the 5-mode
    checkpoint damage matrix) and :func:`tear_wal` (torn final write);
  * :func:`poisson_arrivals` — the open-loop load generator (latency is
    measured from the SCHEDULED arrival, so queueing delay under overload
    is charged to the server, not hidden by closed-loop self-throttling).
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import numpy as np

from repro.launch import pool as pool_mod


class FaultInjected(RuntimeError):
    """The injected failure (stands in for a device error / IO fault)."""


class FaultInjector:
    """Deterministic fault schedules on the pool's named fault points.

    Use as a context manager; hooks are installed on ``__enter__`` and
    cleared on ``__exit__``. ``calls``/``fired`` count per-point activity
    so tests can assert a fault actually exercised the path it targeted.
    """

    def __init__(self, seed: int = 0):
        self._rng = np.random.default_rng(seed)
        self._plans: Dict[str, dict] = {}
        self.calls: Dict[str, int] = {}
        self.fired: Dict[str, int] = {}

    # -- schedule builders (chainable) --------------------------------------
    def fail_next(self, point: str, n: int = 1,
                  stream: Optional[str] = None,
                  exc: type = FaultInjected) -> "FaultInjector":
        """Fail the next ``n`` matching calls, then heal (transient)."""
        self._plans[point] = {"kind": "next", "n": int(n), "stream": stream,
                              "exc": exc}
        return self

    def fail_always(self, point: str, stream: Optional[str] = None,
                    exc: type = FaultInjected) -> "FaultInjector":
        """Fail every matching call until healed (persistent outage)."""
        self._plans[point] = {"kind": "always", "stream": stream, "exc": exc}
        return self

    def fail_calls(self, point: str, indices,
                   stream: Optional[str] = None,
                   exc: type = FaultInjected) -> "FaultInjector":
        """Fail the i-th matching calls (0-based) — scripted bursts."""
        self._plans[point] = {"kind": "calls", "set": set(map(int, indices)),
                              "stream": stream, "exc": exc}
        return self

    def fail_prob(self, point: str, p: float,
                  stream: Optional[str] = None,
                  exc: type = FaultInjected) -> "FaultInjector":
        """Fail each matching call with seeded probability ``p``."""
        self._plans[point] = {"kind": "prob", "p": float(p),
                              "stream": stream, "exc": exc}
        return self

    def heal(self, point: str) -> "FaultInjector":
        """Clear the schedule for one point (fault repaired mid-run) —
        the installed hook stays but its plan lookup now finds nothing."""
        self._plans.pop(point, None)
        return self

    def kill_host(self, pool, hid: int, at: int = 0,
                  point: str = "host_op",
                  stream: Optional[str] = None) -> "FaultInjector":
        """Kill one scale-out host at the ``at``-th matching call of
        ``point`` (0-based) — the deterministic host-loss schedule.
        Unlike the failure kinds this does NOT raise: it calls
        ``pool.kill_host(hid)`` and lets the interrupted operation fail
        (or survive) exactly as a real machine loss would — the pool sees
        ``HostDownError`` / pending backlog, never a synthetic exception.
        One-shot: later matching calls are no-ops."""
        self._plans[point] = {"kind": "kill", "pool": pool, "hid": int(hid),
                              "at": int(at), "stream": stream}
        return self

    # -- hook plumbing -------------------------------------------------------
    def _hook(self, point: str):
        def fire(stream: str):
            self.calls[point] = self.calls.get(point, 0) + 1
            plan = self._plans.get(point)
            if plan is None:
                return
            if (plan["stream"] is not None
                    and plan["stream"] != stream
                    # host_op labels are "<stream>@h<hid>" — match on the
                    # stream half so schedules can target one tenant
                    and plan["stream"] != stream.split("@")[0]):
                return
            idx = self.calls[point] - 1
            kind = plan["kind"]
            if kind == "kill":
                if idx == plan["at"]:
                    self.fired[point] = self.fired.get(point, 0) + 1
                    plan["pool"].kill_host(plan["hid"])
                return
            hit = (kind == "always"
                   or (kind == "next" and plan["n"] > 0)
                   or (kind == "calls" and idx in plan["set"])
                   or (kind == "prob" and self._rng.random() < plan["p"]))
            if not hit:
                return
            if kind == "next":
                plan["n"] -= 1
            self.fired[point] = self.fired.get(point, 0) + 1
            raise plan["exc"](f"injected {point} fault "
                              f"(stream={stream}, call={idx})")
        return fire

    def __enter__(self) -> "FaultInjector":
        for point in pool_mod.FAULT_POINTS:
            pool_mod.install_fault_hook(point, self._hook(point))
        return self

    def __exit__(self, *exc_info):
        pool_mod.clear_fault_hooks()
        return False


# ---------------------------------------------------------------------------
# corruption generators
# ---------------------------------------------------------------------------

CKPT_CORRUPTIONS = ("flip_byte", "truncate_array", "delete_meta",
                    "tmp_dir", "delete_array")


def _step_dirs(directory: str):
    return sorted(d for d in os.listdir(directory)
                  if d.startswith("step_") and not d.endswith(".tmp"))


def corrupt_checkpoint(directory: str, mode: str,
                       step_dir: Optional[str] = None) -> str:
    """Damage the newest (or given) checkpoint step under ``directory``.

    Modes (the corruption matrix): ``flip_byte`` (crc must catch),
    ``truncate_array`` (short read), ``delete_meta`` (no manifest),
    ``tmp_dir`` (leftover partial step_N.tmp from a crashed save — must be
    IGNORED, the intact steps still restore), ``delete_array`` (partial
    checkpoint, an array file missing). Returns the path touched.
    """
    if mode not in CKPT_CORRUPTIONS:
        raise ValueError(f"unknown corruption mode {mode!r}")
    steps = _step_dirs(directory)
    target = os.path.join(directory, step_dir or steps[-1])
    if mode == "tmp_dir":
        tmp = os.path.join(directory, "step_9999999999.tmp")
        os.makedirs(tmp, exist_ok=True)
        with open(os.path.join(tmp, "half_written.npy"), "wb") as f:
            f.write(b"\x93NUMPY partial")
        return tmp
    npys = sorted(p for p in os.listdir(target) if p.endswith(".npy"))
    if mode == "flip_byte":
        path = os.path.join(target, npys[0])
        with open(path, "r+b") as f:
            f.seek(max(os.path.getsize(path) // 2, 80))  # data, not header
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
        return path
    if mode == "truncate_array":
        path = os.path.join(target, npys[0])
        with open(path, "r+b") as f:
            f.truncate(max(os.path.getsize(path) // 2, 1))
        return path
    if mode == "delete_meta":
        path = os.path.join(target, "meta.json")
        os.remove(path)
        return path
    path = os.path.join(target, npys[0])   # delete_array
    os.remove(path)
    return path


def tear_wal(path: str, drop_bytes: int = 7) -> int:
    """Tear the WAL's final record (crash mid-write): truncate the last
    ``drop_bytes`` bytes. Returns the new size."""
    size = os.path.getsize(path)
    new = max(size - int(drop_bytes), 0)
    with open(path, "r+b") as f:
        f.truncate(new)
    return new


# ---------------------------------------------------------------------------
# open-loop load generator
# ---------------------------------------------------------------------------

def poisson_arrivals(rate_hz: float, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``n`` open-loop Poisson arrival times (seconds from start) at
    ``rate_hz`` — exponential inter-arrivals, cumulative."""
    gaps = rng.exponential(1.0 / float(rate_hz), int(n))
    return np.cumsum(gaps)
