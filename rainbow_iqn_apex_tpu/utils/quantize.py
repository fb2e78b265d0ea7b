"""Quantized policy inference + delta-compressed weight distribution.

Two costs grow with the fleet, not the model (Ape-X, arXiv:1803.00933):
every actor lane / serving engine runs a full-precision forward pass per
frame, and every weight publish ships full fp32 (or bf16-cast) params to
every subscriber.  QuaRL (arXiv:1910.01055) shows post-training int8 policy
inference holds RL returns; this module supplies both halves:

- **Weight quantization** (`quantize_tree` / `dequantize_tree`): symmetric
  per-channel int8 for every leaf of a param pytree — scale = max|w| / 127
  per output channel (last axis) for rank>=2 tensors, per-tensor for
  vectors.  The jax twins (`quantize_tree_jax`, `dequantize_tree_jax`)
  run the same math in-graph, so a quantized publish ships int8 over
  ICI/DCN (4x less than fp32) and the act step dequantizes on the fly
  inside its own XLA executable.  An optional fp8 cast
  (`serve_quantize="fp8"`) sits behind the `ml_dtypes` availability guard.
- **Delta compression** (`DeltaEncoder` / `DeltaDecoder`): a periodic full
  base snapshot (bf16 when ml_dtypes is present, else fp32) plus int8
  per-tensor-scaled deltas against the *reconstructed* previous state.
  Encoding is closed-loop: the encoder quantizes the delta against what
  subscribers actually hold, so encoder and every in-sync decoder agree
  **bit-exact** after each packet and quantization error can never
  accumulate across the chain.  A decoder that missed a packet raises
  `DeltaChainBroken` and resyncs by replaying the chain-from-base the
  encoder keeps (`WeightMailbox` / `FleetRollout` wire this up).
- **Accuracy gate** (`greedy_agreement`): quantized params serve traffic
  only after their greedy actions agree with the fp32 policy on a
  calibration batch (threshold `cfg.quant_agreement_min`); a failed gate
  falls back to fp32 with a reasoned ``quant_fallback`` row.

This module is deliberately **jax-free at import** (the `utils` package
contract): the numpy codec runs in router front-ends and mailbox readers
that own no device; everything jax lives behind function-local imports.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

try:  # bf16 base snapshots + the fp8 serve path ride on ml_dtypes; its
    import ml_dtypes  # absence degrades to fp32 bases and refuses fp8
    HAVE_ML_DTYPES = True
except ImportError:  # pragma: no cover - the build image bakes it in
    ml_dtypes = None
    HAVE_ML_DTYPES = False

QUANT_MODES = ("off", "int8", "fp8")
_INT8_MAX = 127.0


def fp8_available() -> bool:
    """fp8 serving needs ml_dtypes' float8_e4m3fn (jax shares the dtype)."""
    return HAVE_ML_DTYPES and hasattr(ml_dtypes, "float8_e4m3fn")


def check_mode(mode: str) -> str:
    if mode not in QUANT_MODES:
        raise ValueError(f"serve_quantize must be one of {QUANT_MODES}, "
                         f"got {mode!r}")
    if mode == "fp8" and not fp8_available():
        raise ValueError("serve_quantize='fp8' needs ml_dtypes.float8_e4m3fn "
                         "(not available in this environment)")
    return mode


# ------------------------------------------------------------ tree plumbing
# Param pytrees here are nested string-keyed mappings with array leaves (the
# flax params dict).  A hand-rolled flatten keeps this file importable
# without jax; paths are "/"-joined sorted keys, so flatten order — and
# therefore packet layout — is deterministic across processes.

def flatten_tree(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, Mapping):
        for key in sorted(tree):
            out.update(flatten_tree(tree[key], f"{prefix}{key}/"))
        return out
    out[prefix.rstrip("/")] = np.asarray(tree)
    return out


def unflatten_tree(flat: Mapping[str, np.ndarray]) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = root
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return root


def tree_bytes(tree: Any) -> int:
    """Logical payload bytes of a pytree (what a publish would ship)."""
    return int(sum(leaf.nbytes for leaf in flatten_tree(tree).values()))


# -------------------------------------------------- symmetric int8 (numpy)
def quantize_array(arr: np.ndarray,
                   per_channel: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric int8: returns (q int8, scale f32).  Rank>=2 arrays get one
    scale per OUTPUT channel (last axis — the flax kernel convention); rank
    0/1 arrays one per-tensor scale.  An all-zero channel gets scale 1 so
    dequantize is exact (0 -> 0), never 0/0."""
    arr = np.asarray(arr, np.float32)
    if per_channel and arr.ndim >= 2:
        axes = tuple(range(arr.ndim - 1))
        max_abs = np.max(np.abs(arr), axis=axes)  # [C]
    else:
        max_abs = np.max(np.abs(arr)) if arr.size else np.float32(0.0)
    scale = np.where(max_abs > 0, max_abs / _INT8_MAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(arr / scale), -_INT8_MAX, _INT8_MAX).astype(np.int8)
    return q, np.atleast_1d(scale)


def dequantize_array(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    scale = np.asarray(scale, np.float32)
    if scale.size == 1:
        scale = scale.reshape(())
    return (q.astype(np.float32) * scale).astype(np.float32)


def quantize_tree(tree: Any, per_channel: bool = True) -> Dict[str, Any]:
    """Pytree -> same-shape pytree with each leaf replaced by
    ``{"q": int8, "s": f32 scale}`` (device_put- and jax.tree-friendly)."""
    flat = flatten_tree(tree)
    qflat = {}
    for path, leaf in flat.items():
        q, s = quantize_array(leaf, per_channel=per_channel)
        qflat[path] = {"q": q, "s": s}
    return unflatten_tree(qflat)


def dequantize_tree(qtree: Any) -> Dict[str, Any]:
    """Inverse of `quantize_tree` (host/numpy path)."""
    def walk(node):
        if isinstance(node, Mapping) and set(node) == {"q", "s"}:
            return dequantize_array(np.asarray(node["q"]),
                                    np.asarray(node["s"]))
        return {k: walk(v) for k, v in node.items()}

    return walk(qtree)


def is_quantized_tree(tree: Any) -> bool:
    """True when ``tree`` is a `quantize_tree` output (its leaves are
    {"q","s"} cells) — how act paths tell qparams from plain params."""
    node = tree
    while isinstance(node, Mapping):
        if set(node) == {"q", "s"}:
            return True
        if not node:
            return False
        node = node[sorted(node)[0]]
    return False


def greedy_agreement(actions_a: np.ndarray, actions_b: np.ndarray) -> float:
    """Fraction of identical greedy actions — the accuracy gate's metric."""
    a = np.asarray(actions_a).reshape(-1)
    b = np.asarray(actions_b).reshape(-1)
    if a.shape != b.shape:
        raise ValueError(f"action shape mismatch {a.shape} vs {b.shape}")
    if a.size == 0:
        return 0.0
    return float(np.mean(a == b))


# -------------------------------------------------------- jax-side helpers
def quantize_tree_jax(params: Any) -> Any:
    """In-graph twin of `quantize_tree` (jit-able): per-output-channel
    symmetric int8.  Ships 4x fewer bytes per publish than fp32 and the
    actor/serve act step dequantizes in its own executable."""
    import jax
    import jax.numpy as jnp

    def quant(leaf):
        x = leaf.astype(jnp.float32)
        if x.ndim >= 2:
            axes = tuple(range(x.ndim - 1))
            max_abs = jnp.max(jnp.abs(x), axis=axes)
        else:
            max_abs = jnp.max(jnp.abs(x))
        scale = jnp.where(max_abs > 0, max_abs / _INT8_MAX, 1.0)
        q = jnp.clip(jnp.rint(x / scale), -_INT8_MAX, _INT8_MAX)
        return {"q": q.astype(jnp.int8),
                "s": jnp.atleast_1d(scale.astype(jnp.float32))}

    return jax.tree.map(quant, params)


def cast_tree_fp8(params: Any) -> Any:
    """fp8 (e4m3) cast of every leaf — the `serve_quantize="fp8"` payload.
    Same {"q","s"} cell shape as int8 (scale 1) so one act wrapper serves
    both modes."""
    import jax
    import jax.numpy as jnp

    if not fp8_available():  # pragma: no cover - guarded by check_mode
        raise RuntimeError("fp8 quantization needs ml_dtypes.float8_e4m3fn")
    fp8 = jnp.dtype(ml_dtypes.float8_e4m3fn)
    return jax.tree.map(
        lambda x: {"q": x.astype(fp8), "s": jnp.ones((1,), jnp.float32)},
        params,
    )


def quantize_for_mode(params: Any, mode: str) -> Any:
    if mode == "int8":
        return quantize_tree_jax(params)
    if mode == "fp8":
        return cast_tree_fp8(params)
    raise ValueError(f"no quantized payload for mode {mode!r}")


def dequantize_tree_jax(qtree: Any, dtype: Any = None) -> Any:
    """In-graph dequantize of a `quantize_tree_jax`/`cast_tree_fp8` tree.
    XLA fuses this into the act executable, so weights stay int8/fp8 in HBM
    and the multiply-by-scale rides the first use of each tensor."""
    import jax
    import jax.numpy as jnp

    dt = jnp.float32 if dtype is None else dtype

    def dequant(cell):
        q, s = cell["q"], cell["s"]
        # scale broadcasts over the last axis (per-channel [C]) or the whole
        # tensor (per-tensor [1]); the reshape restores rank-0 leaves
        return jnp.reshape(q.astype(dt) * s.astype(dt), q.shape)

    return jax.tree.map(dequant, qtree,
                        is_leaf=lambda n: isinstance(n, dict)
                        and set(n) == {"q", "s"})


def wrap_act_quantized(act_fn: Callable) -> Callable:
    """Wrap an act step so its first argument is a quantized tree; the
    dequantize happens inside the same (to-be-jitted) function, i.e. inside
    the same XLA executable per bucket."""
    def act_q(qparams, *args, **kwargs):
        return act_fn(dequantize_tree_jax(qparams), *args, **kwargs)

    return act_q


# --------------------------------------------------------- delta packets
class DeltaChainBroken(RuntimeError):
    """The decoder was handed a delta it cannot apply (missed packet, fresh
    subscriber): resync from the chain-from-base the encoder keeps."""


@dataclasses.dataclass
class WeightPacket:
    """One publish on the wire: a full base snapshot or an int8 delta.

    ``leaves`` maps flat tree paths to ``(payload, scale)``; base packets
    carry (bf16-or-fp32 array, None), delta packets (int8 array, one
    per-tensor f32 scale).  ``prev_version`` is the version this delta
    applies on top of (-1 for a base).  Packets are value objects — safe to
    fan out to N subscribers concurrently."""

    kind: str  # "base" | "delta"
    version: int
    prev_version: int
    base_version: int
    leaves: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]

    def nbytes(self) -> int:
        """Logical wire bytes: payload + scales."""
        total = 0
        for data, scale in self.leaves.values():
            total += data.nbytes + (scale.nbytes if scale is not None else 0)
        return int(total)


def _packet_arrays(packet: WeightPacket) -> Dict[str, np.ndarray]:
    """The npz array dict for one packet (shared by the file and wire
    serialisations, so a packet saved to disk and one framed over a socket
    are byte-identical payloads)."""
    arrays: Dict[str, np.ndarray] = {}
    for leaf_path, (data, scale) in packet.leaves.items():
        if HAVE_ML_DTYPES and data.dtype == np.dtype(ml_dtypes.bfloat16):
            # np.load cannot round-trip ml_dtypes' bfloat16; ship the raw
            # bits as uint16 under a marker key and re-view on load
            arrays[f"b::{leaf_path}"] = data.view(np.uint16)
        else:
            arrays[f"d::{leaf_path}"] = data
        if scale is not None:
            arrays[f"s::{leaf_path}"] = scale
    arrays["__meta__"] = np.array(
        [packet.version, packet.prev_version, packet.base_version,
         1 if packet.kind == "base" else 0], np.int64)
    return arrays


def _packet_from_npz(z) -> WeightPacket:
    meta = z["__meta__"]
    leaves: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]] = {}
    for key in z.files:
        if not key.startswith(("d::", "b::")):
            continue
        leaf_path = key[3:]
        data = z[key]
        if key.startswith("b::"):
            data = data.view(np.dtype(ml_dtypes.bfloat16))
        scale_key = f"s::{leaf_path}"
        leaves[leaf_path] = (
            data, z[scale_key] if scale_key in z.files else None
        )
    return WeightPacket(
        kind="base" if int(meta[3]) else "delta",
        version=int(meta[0]), prev_version=int(meta[1]),
        base_version=int(meta[2]), leaves=leaves,
    )


def save_packet(packet: WeightPacket, path: str) -> None:
    """One .npz per packet (WeightMailbox's payload files).  Written via
    tmp + rename so a reader never sees a torn file."""
    import os

    tmp = path + ".tmp.npz"
    with open(tmp, "wb") as fh:
        np.savez(fh, **_packet_arrays(packet))
    os.replace(tmp, path)


def load_packet(path: str) -> WeightPacket:
    with np.load(path, allow_pickle=False) as z:
        return _packet_from_npz(z)


def packet_to_bytes(packet: WeightPacket) -> bytes:
    """In-memory npz serialisation — the wire payload the cross-host
    rollout frames over serving/net (same bytes `save_packet` writes)."""
    import io

    buf = io.BytesIO()
    np.savez(buf, **_packet_arrays(packet))
    return buf.getvalue()


def packet_from_bytes(data: bytes) -> WeightPacket:
    import io

    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        return _packet_from_npz(z)


def params_packet(params: Any, version: int) -> WeightPacket:
    """An UNCOMPRESSED full-fp32 base packet for ``params`` — the wire shape
    of a compression="off" rollout (`RemoteEngine.adopt`): the decode is a
    plain fp32 round-trip, so the remote engine adopts bit-exact params
    without holding any delta-chain state."""
    flat = {p: np.asarray(leaf, np.float32)
            for p, leaf in flatten_tree(params).items()}
    return WeightPacket(
        kind="base", version=int(version), prev_version=-1,
        base_version=int(version),
        leaves={p: (leaf, None) for p, leaf in flat.items()},
    )


def tree_digest(tree: Any) -> str:
    """Order-stable sha256 over a param pytree's fp32 leaf bytes — the
    bit-exactness witness for cross-host rollouts (publisher reconstruction
    vs every engine's adopted params).  jax arrays are pulled to host."""
    import hashlib

    h = hashlib.sha256()
    for path in sorted(flat := flatten_tree(tree)):
        arr = np.ascontiguousarray(np.asarray(flat[path], np.float32))
        h.update(path.encode("utf-8"))
        h.update(arr.tobytes())
    return h.hexdigest()


def _base_dtype():
    """Base snapshots ship bf16 when ml_dtypes is importable (half the
    bytes, and training already broadcasts bf16 — cfg.bf16_weight_sync);
    fp32 otherwise.  The choice is per-encoder, stamped into the packets."""
    return np.dtype(ml_dtypes.bfloat16) if HAVE_ML_DTYPES else np.float32


class DeltaEncoder:
    """Closed-loop delta encoder for versioned weight publishes.

    Every `base_interval`-th publish emits a full base snapshot; the ones
    between emit int8 per-tensor deltas against `self._recon` — the state a
    decoder that applied every packet holds, NOT the true fp32 params.
    Quantizing against the reconstruction makes encoder and subscribers
    agree bit-exact after every packet and bounds drift at one delta's
    quantization error regardless of chain length.

    `chain()` returns the packets since (and including) the current base —
    what a late joiner or a gap-hit decoder replays to resync.
    """

    def __init__(self, base_interval: int = 10):
        self.base_interval = max(int(base_interval), 1)
        self.base_dtype = _base_dtype()
        self._recon: Optional[Dict[str, np.ndarray]] = None
        self._chain: List[WeightPacket] = []
        self.version = -1
        self._since_base = 0
        self.publishes = 0
        self.bytes_total = 0

    def encode(self, params: Any, version: int) -> WeightPacket:
        if version <= self.version:
            raise ValueError(
                f"delta encoder is monotone: version {version} <= "
                f"current {self.version}")
        flat = {p: np.asarray(leaf, np.float32)
                for p, leaf in flatten_tree(params).items()}
        make_base = (
            self._recon is None
            or self._since_base >= self.base_interval
            or sorted(flat) != sorted(self._recon)  # reshaped model: resync
        )
        if make_base:
            leaves = {p: (leaf.astype(self.base_dtype), None)
                      for p, leaf in flat.items()}
            # the decoder holds the dtype-rounded values; so must we
            self._recon = {p: data.astype(np.float32)
                           for p, (data, _) in leaves.items()}
            packet = WeightPacket(
                kind="base", version=int(version), prev_version=-1,
                base_version=int(version), leaves=leaves,
            )
            self._chain = [packet]
            self._since_base = 1
        else:
            leaves = {}
            base_version = self._chain[0].base_version
            for path, leaf in flat.items():
                delta = leaf - self._recon[path]
                q, s = quantize_array(delta, per_channel=False)
                leaves[path] = (q, s)
                self._recon[path] = (
                    self._recon[path] + dequantize_array(q, s)
                ).astype(np.float32)
            packet = WeightPacket(
                kind="delta", version=int(version),
                prev_version=self.version, base_version=base_version,
                leaves=leaves,
            )
            self._chain.append(packet)
            self._since_base += 1
        self.version = int(version)
        self.publishes += 1
        self.bytes_total += packet.nbytes()
        return packet

    def chain(self) -> List[WeightPacket]:
        return list(self._chain)

    def reconstructed(self) -> Dict[str, Any]:
        """The fp32 tree every in-sync subscriber currently holds."""
        if self._recon is None:
            raise RuntimeError("nothing encoded yet")
        return unflatten_tree({p: leaf.copy()
                               for p, leaf in self._recon.items()})


class DeltaDecoder:
    """Subscriber state: applies base/delta packets, detects chain gaps."""

    def __init__(self):
        self.version = -1
        self._recon: Optional[Dict[str, np.ndarray]] = None

    def apply(self, packet: WeightPacket) -> Dict[str, Any]:
        """Apply one packet; returns the reconstructed fp32 param tree.
        Backward/duplicate packets raise ValueError (the mailbox mirror of
        FleetRollout's refused_backward); a delta whose prev_version is not
        the held version raises `DeltaChainBroken`."""
        if packet.version <= self.version:
            raise ValueError(
                f"refusing backward/duplicate weight packet "
                f"{packet.version} (holding {self.version})")
        if packet.kind == "base":
            self._recon = {p: data.astype(np.float32)
                           for p, (data, _) in packet.leaves.items()}
        else:
            if self._recon is None or packet.prev_version != self.version:
                raise DeltaChainBroken(
                    f"delta v{packet.version} applies on v{packet.prev_version}, "
                    f"holding v{self.version}: resync from base")
            for path, (q, s) in packet.leaves.items():
                if path not in self._recon:
                    raise DeltaChainBroken(f"unknown leaf {path!r}: resync")
                self._recon[path] = (
                    self._recon[path] + dequantize_array(q, s)
                ).astype(np.float32)
        self.version = int(packet.version)
        return self.params()

    def apply_chain(self, packets: List[WeightPacket]) -> Dict[str, Any]:
        """Replay a chain-from-base, skipping packets already held — the
        late-joiner / gap-recovery path.  The chain's base resets state, so
        this always converges to the encoder's reconstruction."""
        if not packets:
            raise DeltaChainBroken("empty chain")
        for packet in packets:
            if packet.version <= self.version:
                continue  # already held (idempotent catch-up)
            if packet.kind == "delta" and packet.prev_version != self.version:
                # mid-chain join without the base applied first
                raise DeltaChainBroken(
                    f"chain gap at v{packet.version} (holding v{self.version})")
            self.apply(packet)
        return self.params()

    def params(self) -> Dict[str, Any]:
        if self._recon is None:
            raise DeltaChainBroken("no base applied yet")
        return unflatten_tree({p: leaf.copy()
                               for p, leaf in self._recon.items()})
