"""The host<->device sync seam: every *sanctioned* blocking device->host
materialization in the learner hot path goes through this module, and tests
can statically forbid everything else.

Why a seam at all: the learner loop's throughput floor is the device step
time only while the host never blocks on a device value mid-loop
(docs/PERFORMANCE.md sync-point inventory).  One reintroduced
``float(info["loss"])`` or ``int(state.step)`` silently re-serializes the
whole pipeline — the regression this repo once shipped (a per-step
``float(loss)`` in the learn loop).  The seam makes that failure loud:

- ``to_host(x)`` / ``scalar(x)``: the sanctioned materialization calls
  (WritebackRing retirement, supervisor snapshots, cadence reads).  Inside a
  ``forbid_host_sync()`` region they only work under ``sanctioned()``.
- ``check_host_work(tag)``: the same fence for tagged host-side hot-path
  WORK rather than transfers — host replay sampling joined the forbidden
  set when the device sample frontier landed (replay/frontier.py).
- ``forbid_host_sync()``: the tier-1 guard context.  It layers two fences:
  (1) ``jax.transfer_guard_device_to_host("disallow")`` — catches real
  device->host copies on accelerator backends; vacuous on the CPU platform
  where host "transfers" are zero-copy, hence (2) a patch of
  ``ArrayImpl._value`` — the property behind ``float()``/``int()``/
  ``.item()``/``__bool__`` on jax arrays — that raises ``HostSyncError``
  for the guarded thread.  Plain ``np.asarray`` of a CPU-backed jax array
  goes through the buffer protocol below any Python hook and cannot be
  caught on CPU; the write-back lag determinism test (tests/test_writeback)
  covers that hole from the other side.

Thread story: the forbid/sanction flags are thread-local, so the guard
constrains only the thread that entered it — the prefetch worker and the
stall watchdog are unaffected.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any

import numpy as np


class HostSyncError(RuntimeError):
    """A blocking device->host materialization inside a no-sync region."""


_tls = threading.local()


def _forbidden() -> bool:
    return (
        getattr(_tls, "forbid", 0) > 0 and getattr(_tls, "sanction", 0) == 0
    )


@contextlib.contextmanager
def sanctioned():
    """Mark the enclosed block as an allowed sync point (ring retirement,
    snapshot capture, cadence reads).  Composes with an enclosing
    ``forbid_host_sync()``: transfers inside are allowed again."""
    import jax

    _tls.sanction = getattr(_tls, "sanction", 0) + 1
    try:
        with jax.transfer_guard_device_to_host("allow"):
            yield
    finally:
        _tls.sanction -= 1


def to_host(x: Any) -> np.ndarray:
    """Materialize a (possibly device) array on host — THE sanctioned
    device->host array copy of the hot path."""
    if isinstance(x, np.ndarray):
        return x
    if _forbidden():
        raise HostSyncError(
            "to_host() outside a sanctioned() block inside a no-sync region"
        )
    with sanctioned():
        return np.asarray(x)


def check_host_work(tag: str) -> None:
    """Forbidden-set membership check for tagged host-side hot-path WORK —
    not a transfer, but work the zero-sync learner thread must delegate.
    Replay SAMPLING joined the set with the device sample frontier
    (replay/frontier.py): ``PrioritizedReplay.sample`` /
    ``ShardedReplay.sample`` / ``SequenceReplay.sample`` call this, so a
    learner thread inside ``forbid_host_sync()`` that walks a host sum-tree
    per step (instead of consuming the sample-ahead pusher's device-drawn
    batches) fails tier-1 loudly.  Worker threads (prefetcher, pusher) are
    unaffected — the flags are thread-local."""
    if _forbidden():
        raise HostSyncError(
            f"host-side '{tag}' on a thread inside a forbid_host_sync() "
            "region (delegate it to a worker, or wrap a cold-path call in "
            "sanctioned())"
        )


def scalar(x: Any) -> float:
    """Materialize a scalar on host (blocks until the value is ready)."""
    if isinstance(x, (float, int)):
        return float(x)
    if _forbidden():
        raise HostSyncError(
            "scalar() outside a sanctioned() block inside a no-sync region"
        )
    with sanctioned():
        return float(x)


# --------------------------------------------------------------- test guard
_patch_lock = threading.Lock()
_patch_depth = 0
_orig_value = None


def _install_value_guard() -> None:
    """Patch ``ArrayImpl._value`` so float()/int()/.item() on a jax array
    raise inside this thread's forbidden region.  Idempotent/refcounted;
    other threads (prefetcher, watchdog) never see the flag."""
    global _patch_depth, _orig_value
    from jax._src import array as jarray

    with _patch_lock:
        if _patch_depth == 0:
            _orig_value = jarray.ArrayImpl.__dict__["_value"]
            orig = _orig_value

            def _guarded(self):
                if _forbidden():
                    raise HostSyncError(
                        "blocking device->host scalar materialization "
                        "(float/int/item on a jax array) inside a "
                        "forbid_host_sync() region"
                    )
                return orig.fget(self)

            jarray.ArrayImpl._value = property(_guarded)
        _patch_depth += 1


def _remove_value_guard() -> None:
    global _patch_depth
    from jax._src import array as jarray

    with _patch_lock:
        _patch_depth -= 1
        if _patch_depth == 0 and _orig_value is not None:
            jarray.ArrayImpl._value = _orig_value


@contextlib.contextmanager
def forbid_host_sync():
    """Tier-1 static guard: inside this context, any blocking device->host
    materialization on the current thread outside ``sanctioned()`` raises
    ``HostSyncError`` (scalar conversions on every backend; array transfers
    on non-CPU backends via jax's transfer guard)."""
    import jax

    _install_value_guard()
    _tls.forbid = getattr(_tls, "forbid", 0) + 1
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    finally:
        _tls.forbid -= 1
        _remove_value_guard()
