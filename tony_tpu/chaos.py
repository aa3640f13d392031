"""Chaos-injection harness: scripted control-plane faults (jax-free).

Extends the ``TONY_CKPT_CRASH`` idiom (:mod:`tony_tpu.ckpt.format`) from
one checkpoint-commit fault to a vocabulary the whole control plane
consults, so the elastic-resize pins are machine-checkable: a test arms
a fault schedule through ``TONY_CHAOS_*`` env vars and the production
code paths fire it at the instrumented sites —

* ``TONY_CHAOS_KILL_STEP=k`` — SIGKILL this process as TRAINING step
  ``k`` begins (:func:`tony_tpu.train.train_loop` consults
  :func:`kill_point` each step): the scripted preemption.
* ``TONY_CHAOS_HB_DROP=n`` — swallow the first ``n`` executor heartbeat
  sends (:func:`drop_heartbeat`): a flaky heartbeat window that must NOT
  mark a healthy task lost now that the RPC client backs off and
  retries.
* ``TONY_CHAOS_RPC_DELAY_S=s`` (+ optional ``TONY_CHAOS_RPC_DELAY_CALLS=n``,
  default 1) — stall the first ``n`` RPC calls ``s`` seconds before they
  touch the wire (:func:`rpc_delay` in ``RpcClient.call``): transient
  transport latency.
* ``TONY_CHAOS_CRASH=<site>`` — SIGKILL at a named crash site
  (:func:`crash_point`); the history-plane rotation path declares
  ``rotate_before_stage`` / ``rotate_after_stage`` / ``rotate_after_replace``
  so the stage-and-rename sweep can prove "old log or new log, never a
  torn file"; the continuous-publication plane declares
  ``publish_before_stage`` / ``publish_after_stage`` /
  ``publish_after_replace`` around the pointer-file commit
  (:func:`tony_tpu.publish.publish_step`) and ``swap_before_restore`` /
  ``swap_after_restore`` / ``swap_before_flip`` / ``swap_after_flip``
  around a replica's hot swap (:meth:`tony_tpu.serve.replica.Replica.
  hot_swap`) so the sweep can prove "old weights or new weights, never
  a mixed-version replica". (Checkpoint commits keep their original
  ``TONY_CKPT_CRASH`` phases.)

Every probe is a cheap env read that no-ops when unarmed — an unarmed
process pays one ``os.environ.get`` per site. Malformed specs raise
``ValueError`` loudly: silently ignoring a typoed fault schedule would
turn a failing chaos test into a vacuous pass.

In-process tests can replace the irreversible faults with module hooks
(the ``CRASH_HOOK`` idiom): ``KILL_HOOK``/``CRASH_HOOK`` observe the
fault instead of delivering SIGKILL, ``SLEEP_HOOK`` replaces the delay
sleep. "First n" schedules count across call sites through a
lock-guarded module counter table — call :func:`reset` between tests.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Callable, Dict, Optional

__all__ = [
    "ENV_KILL_STEP", "ENV_HB_DROP", "ENV_RPC_DELAY_S",
    "ENV_RPC_DELAY_CALLS", "ENV_CRASH",
    "kill_point", "drop_heartbeat", "rpc_delay", "crash_point", "reset",
]

ENV_KILL_STEP = "TONY_CHAOS_KILL_STEP"
ENV_HB_DROP = "TONY_CHAOS_HB_DROP"
ENV_RPC_DELAY_S = "TONY_CHAOS_RPC_DELAY_S"
ENV_RPC_DELAY_CALLS = "TONY_CHAOS_RPC_DELAY_CALLS"
ENV_CRASH = "TONY_CHAOS_CRASH"

# Test hooks: when set, the hook fires INSTEAD of the real fault
# (SIGKILL / sleep), so in-process tests can observe or redirect it.
KILL_HOOK: Optional[Callable[[int], None]] = None
CRASH_HOOK: Optional[Callable[[str], None]] = None
SLEEP_HOOK: Optional[Callable[[float], None]] = None

_lock = threading.Lock()    # guards _counters (probe sites span threads)
_counters: Dict[str, int] = {}


def reset() -> None:
    """Clear the "first n" schedule counters (test epilogue)."""
    with _lock:
        _counters.clear()


def _count(key: str) -> int:
    with _lock:
        _counters[key] = _counters.get(key, 0) + 1
        return _counters[key]


def _int_env(name: str) -> Optional[int]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"chaos schedule {name}={raw!r} is not an integer") from None


def _float_env(name: str) -> Optional[float]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        val = float(raw)
    except ValueError:
        raise ValueError(
            f"chaos schedule {name}={raw!r} is not a number") from None
    if val != val or val < 0:
        raise ValueError(
            f"chaos schedule {name}={raw!r} must be >= 0")
    return val


def kill_point(step: int) -> None:
    """SIGKILL this process if ``TONY_CHAOS_KILL_STEP`` names ``step``
    (the scripted preemption: the scheduler's kill -9, not a clean
    exit). Consulted by ``train_loop`` as each step begins, so the kill
    lands AFTER the previous step's work and BEFORE any of step ``k``'s
    examples are consumed."""
    at = _int_env(ENV_KILL_STEP)
    if at is None or step != at:
        return
    if KILL_HOOK is not None:
        KILL_HOOK(step)
        return
    os.kill(os.getpid(), signal.SIGKILL)


def drop_heartbeat() -> bool:
    """True if this heartbeat send should be swallowed (the first ``n``
    probes when ``TONY_CHAOS_HB_DROP=n`` is armed)."""
    n = _int_env(ENV_HB_DROP)
    if n is None or n <= 0:
        return False
    return _count("hb_drop") <= n


def rpc_delay() -> None:
    """Stall the first ``TONY_CHAOS_RPC_DELAY_CALLS`` (default 1) RPC
    calls by ``TONY_CHAOS_RPC_DELAY_S`` seconds — injected transport
    latency, counted per logical call (retries of a delayed call are
    not re-delayed: the fault is the network hiccup, not a broken
    peer)."""
    delay = _float_env(ENV_RPC_DELAY_S)
    if delay is None or delay <= 0:
        return
    n = _int_env(ENV_RPC_DELAY_CALLS)
    if _count("rpc_delay") <= (1 if n is None else n):
        (SLEEP_HOOK or time.sleep)(delay)


def crash_point(site: str) -> None:
    """SIGKILL at a named crash site when ``TONY_CHAOS_CRASH`` matches —
    the ``TONY_CKPT_CRASH`` idiom generalized: production code declares
    the site, the test arms exactly one, and the invariant is whatever
    must survive a kill -9 there."""
    if os.environ.get(ENV_CRASH, "") != site:
        return
    if CRASH_HOOK is not None:
        CRASH_HOOK(site)
        return
    os.kill(os.getpid(), signal.SIGKILL)
