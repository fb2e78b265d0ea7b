"""ctypes binding for the C++ replay core (replay/native/*.cc).

v1: sum-tree set/find hot loops (sumtree.cc).  v2 adds the fused per-tick
append and per-batch assembly paths (replay_core.cc).  Builds one shared
library on first use with g++ (toolchain is baked into the image; no
pip/pybind11 needed) and caches it next to the sources.  A build or load
failure raises ``NativeBuildError`` carrying g++'s stderr: whoever asked for
the native core (``use_native_sumtree=True``, the default) gets it or an
error, never a silent NumPy substitute.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from rainbow_iqn_apex_tpu.replay.sumtree import SumTree

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = (
    os.path.join(_HERE, "native", "sumtree.cc"),
    os.path.join(_HERE, "native", "replay_core.cc"),
)
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC")


class NativeBuildError(RuntimeError):
    """The C++ replay core could not be compiled or loaded on this host."""


def _host_signature() -> bytes:
    """What ``-march=native`` resolves to here: the architecture plus the
    CPU's model and feature flags.  Part of the artifact name, so a binary
    compiled for another machine's CPU (a checkout copied between hosts
    carries its untracked ``.so`` files along) is never picked up."""
    lines = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            seen = set()
            for line in f:
                field = line.split(":", 1)[0].strip()
                if field in ("model name", "flags", "Features") and field not in seen:
                    seen.add(field)
                    lines.append(line.strip())
    except OSError:
        lines.append(platform.processor())
    return "\n".join(lines).encode()


def _so_path() -> str:
    """Artifact name keyed by sources, flags and host CPU: any source change,
    fresh checkout or other machine gets its own name and a rebuild."""
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_host_signature())
    return os.path.join(_HERE, "native", f"_replay_{h.hexdigest()[:16]}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_error: Optional[NativeBuildError] = None

_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")


def _compile(so: str) -> None:
    # build under a private name, then rename: a concurrent process never
    # dlopens a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(
            ["g++", *_FLAGS, *_SRCS, "-o", tmp],
            capture_output=True, text=True, timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"could not run g++: {e!r}") from e
    if proc.returncode != 0:
        raise NativeBuildError(
            f"g++ exited {proc.returncode} building the replay core:\n"
            f"{proc.stderr.strip()}")
    os.replace(tmp, so)


def _declare(lib: ctypes.CDLL) -> None:
    lib.st_set.argtypes = [_f64p, ctypes.c_int64, _i64p, _f64p, ctypes.c_int64]
    lib.st_set.restype = None
    lib.st_find_prefix.argtypes = [
        _f64p, ctypes.c_int64, ctypes.c_int64, _f64p, _i64p, ctypes.c_int64,
    ]
    lib.st_find_prefix.restype = None
    lib.st_sample.argtypes = [
        _f64p, ctypes.c_int64, ctypes.c_int64, _f64p, _i64p, _f64p,
        ctypes.c_int64,
    ]
    lib.st_sample.restype = None
    i64 = ctypes.c_int64
    lib.rb_append_tick.argtypes = [
        _u8p, _i32p, _f32p, _u8p, _u8p,  # frames/actions/rewards/term/cuts
        _f64p, i64,  # tree, span
        i64, i64, i64, i64, i64, i64, i64,  # lanes seg pos filled hist n fb
        _u8p, _i32p, _f32p, _u8p,  # new frame/action/reward/terminal
        ctypes.c_void_p, ctypes.c_void_p,  # truncs?, priorities?
        ctypes.c_double, ctypes.c_double,  # eps, omega
        ctypes.POINTER(ctypes.c_double),  # max_priority (inout)
    ]
    lib.rb_append_tick.restype = None
    lib.rb_assemble.argtypes = [
        _u8p, _i32p, _f32p, _u8p, _u8p,
        i64, i64, i64, i64, i64,  # seg filled hist n fb
        _f32p,  # gammas
        _i64p, i64,  # idx, batch
        _u8p, _u8p, _i32p, _f32p, _f32p,  # outputs
    ]
    lib.rb_assemble.restype = None


def _build_and_load() -> ctypes.CDLL:
    """The loaded library; built first if this host has no artifact yet.
    The outcome — library or error — is decided once per process."""
    global _lib, _error
    with _lock:
        if _lib is not None:
            return _lib
        if _error is not None:
            raise _error
        try:
            so = _so_path()
            if not os.path.exists(so):  # name is content+host keyed: exists == fresh
                _compile(so)
            try:
                lib = ctypes.CDLL(so)
            except OSError as e:
                raise NativeBuildError(f"could not load {so}: {e}") from e
        except NativeBuildError as e:
            _error = e
            raise
        _declare(lib)
        _lib = lib
        return _lib


def native_available() -> bool:
    try:
        _build_and_load()
    except NativeBuildError:
        return False
    return True


def loaded_library() -> Optional[str]:
    """Path of the shared library this process is running, or None when the
    native core has not been loaded (chip_smoke.py reports it)."""
    return None if _lib is None else _lib._name


class NativeSumTree(SumTree):
    """Drop-in SumTree with the set/find hot loops in C++.

    Same flat-array layout and numerics as the NumPy SumTree (the fuzz test
    runs both against each other); storage stays a NumPy array so snapshots
    and the rest of the Python API are unchanged.
    """

    def __init__(self, capacity: int):
        super().__init__(capacity)
        self._lib = _build_and_load()

    def set(self, idx: np.ndarray, priority: np.ndarray) -> None:
        idx = np.ascontiguousarray(np.asarray(idx, np.int64).ravel())
        pri = np.ascontiguousarray(
            np.broadcast_to(np.asarray(priority, np.float64).ravel(), idx.shape)
        )
        if idx.size == 0:
            return
        if np.any(pri < 0) or not np.all(np.isfinite(pri)):
            raise ValueError("priorities must be finite and non-negative")
        self._lib.st_set(self.tree, self.span, idx, pri, idx.size)

    def find_prefix(self, mass: np.ndarray) -> np.ndarray:
        mass = np.ascontiguousarray(np.asarray(mass, np.float64).ravel())
        out = np.empty(mass.size, np.int64)
        self._lib.st_find_prefix(self.tree, self.span, self.capacity, mass, out, mass.size)
        return out

    def sample_stratified(
        self, batch_size: int, rng: np.random.Generator
    ) -> Tuple[np.ndarray, np.ndarray]:
        total = self.total
        if total <= 0:
            raise ValueError("cannot sample from an empty tree")
        seg = total / batch_size
        mass = np.ascontiguousarray(
            (np.arange(batch_size) + rng.random(batch_size)) * seg
        )
        idx = np.empty(batch_size, np.int64)
        pri = np.empty(batch_size, np.float64)
        self._lib.st_sample(self.tree, self.span, self.capacity, mass, idx, pri, batch_size)
        return idx, pri / total


class ReplayCore:
    """v2 fused append/assemble over a PrioritizedReplay's own arrays.

    One ctypes call per actor tick (ring writes + every tree update,
    including the truncation-eligibility rule) and one per sampled batch
    (n-step scan + both stack gathers straight into the [B, H, W, hist]
    device layout).  The buffer's NumPy arrays are the single source of
    truth; this object holds no state beyond the library handle.
    """

    def __init__(self, buf):
        self._lib = _build_and_load()
        self._b = buf
        self._fb = buf.frames.shape[1] * buf.frames.shape[2]

    def append_tick(self, frames, actions, rewards, terminals, priorities,
                    truncations) -> float:
        b = self._b
        mp = ctypes.c_double(b.max_priority)
        trunc = (
            None
            if truncations is None
            else np.ascontiguousarray(np.asarray(truncations, bool)).view(np.uint8)
        )
        pri = (
            None
            if priorities is None
            else np.ascontiguousarray(np.asarray(priorities, np.float64))
        )
        self._lib.rb_append_tick(
            b.frames.reshape(b.frames.shape[0], -1),
            b.actions, b.rewards,
            b.terminals.view(np.uint8), b.cuts.view(np.uint8),
            b.tree.tree, b.tree.span,
            b.lanes, b.seg, b.pos, b.filled, b.history, b.n_step, self._fb,
            np.ascontiguousarray(frames, np.uint8).reshape(len(frames), -1),
            np.ascontiguousarray(actions, np.int32),
            np.ascontiguousarray(rewards, np.float32),
            np.ascontiguousarray(np.asarray(terminals, bool)).view(np.uint8),
            None if trunc is None else trunc.ctypes.data_as(ctypes.c_void_p),
            None if pri is None else pri.ctypes.data_as(ctypes.c_void_p),
            b.eps, b.omega, ctypes.byref(mp),
        )
        return mp.value

    def assemble(self, idx: np.ndarray, batch_size: int, out=None):
        """``out`` (obs, next_obs, action, reward, discount), when given,
        receives the rows in place — C-contiguous row slices of a caller's
        batch buffers are accepted, so a shard-sorted gather (the device
        sample frontier's draw returns slot-sorted indices) fills the final
        batch with ZERO extra copies."""
        b = self._b
        h, w = b.frames.shape[1], b.frames.shape[2]
        if out is None:
            obs = np.empty((batch_size, h, w, b.history), np.uint8)
            next_obs = np.empty_like(obs)
            action = np.empty(batch_size, np.int32)
            reward = np.empty(batch_size, np.float32)
            discount = np.empty(batch_size, np.float32)
        else:
            obs, next_obs, action, reward, discount = out
        self._lib.rb_assemble(
            b.frames.reshape(b.frames.shape[0], -1),
            b.actions, b.rewards,
            b.terminals.view(np.uint8), b.cuts.view(np.uint8),
            b.seg, b.filled, b.history, b.n_step, self._fb,
            b._gammas,
            np.ascontiguousarray(idx, np.int64), batch_size,
            obs.reshape(batch_size, -1), next_obs.reshape(batch_size, -1),
            action, reward, discount,
        )
        return obs, next_obs, action, reward, discount
