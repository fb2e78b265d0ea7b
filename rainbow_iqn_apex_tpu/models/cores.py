"""The recurrent core of `R2D2Net`, behind one interface.

A core sits between the conv trunk and the dueling heads:

    core(x [B, T, F] float32, state, resets [B, T] bool) -> (y [B, T, F'], state)
    core.initial_state(batch) -> state      (all zeros; zeroing a lane's
                                             leaves resets that lane)
    core.reset_lanes(state, keep [B]) -> state   the lanes where keep is 0
                                             back at the start, by the
                                             core's cheapest means
    core.stored_width                        width of EACH of the ring's two
                                             stored-state columns
    core.to_stored(state) -> (c, h)          what the ring keeps of a state
    core.from_stored(c, h) -> state          a sequence's start state: the
                                             tree of `initial_state`, where a
                                             leaf that only grows with the
                                             steps (an attention window's
                                             slots, axis 1) may hold fewer

`resets[b, t]` zeroes lane b's state BEFORE step t.  A core is a plain
(hashable) object; called inside `R2D2Net.__call__` it builds its flax
modules in the net's scope, so the LSTM's parameters stay `lstm/cell/...`
leaf for leaf: the leaves of flax's `LSTMCell`, which checkpoints, the ring's
stored states, the benchmark's reference and its seeded weights read by name.

The LSTM is one formula for every T (the actor's tick is T = 1).  The input
side of the gates, `x @ [W_ii | W_if | W_ig | W_io]`, does not depend on the
carry, so it is one `[T, B, F] x [F, 4m]` product before the loop (scope
`lstm_input`); the loop keeps the reset, `h @ W_h + b`, the activations and
the state.  Autodiff of that form stacks the gates' cotangents `[T, B, 4m]` in
the backward loop and takes the input kernels' gradient and `dx` as one
product each after it.  In the loop they were T products of B rows each, at
half the rate or less, and an `f32[F, 4m]` accumulator read and written every
turn (PERF.md, PR 39).  All of it wears the scope `lstm_scan`.

Seven cores: `LSTMCore` (the R2D2 paper's, stored-state replay: the ring keeps
(c, h) of every sequence start), and six over the blocks of
models/mla_moe.py (zero start state: the ring's state columns have width 0,
and a sequence's attention windows start with no slots):
`models/kimi_linear.KimiLinearCore`, `models/deepseek_v3.DeepSeekV3Core`,
`models/qwen3_next.Qwen3NextCore`, `models/ouro.OuroCore`,
`models/lfm2.Lfm2Core` and `models/laguna.LagunaCore`, where F' is the
model's hidden size and not F.
`Config.core_config` names the file of one, which says which by its
`model_type`; none is the LSTM.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import json
import os
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.linen.recurrent import DenseParams

from rainbow_iqn_apex_tpu.obs import device_scopes

LSTMState = Tuple[jnp.ndarray, jnp.ndarray]  # (c, h), each [B, lstm_size]
CORE_STATS = "core_stats"  # flax collection a core sows its counters in


_GATES = "ifgo"  # flax's order; the column blocks of the joined kernels


class _JoinedLSTMCell(nn.Module):
    """The leaves of flax's `LSTMCell`, made as `OptimizedLSTMCell` makes
    them (`cell/{ii,if,ig,io}/kernel [F, m]` lecun-normal,
    `cell/{hi,hf,hg,ho}/{kernel [m, m] orthogonal, bias [m]}`) and handed out
    joined: `W_x [F, 4m]`, `W_h [m, 4m]`, `b [4m]`."""

    features: int

    @nn.compact
    def __call__(self, x, h):
        w_x, _ = zip(*(DenseParams(
            self.features, use_bias=False, name="i" + g)(x) for g in _GATES))
        w_h, b = zip(*(DenseParams(
            self.features, kernel_init=nn.initializers.orthogonal(),
            name="h" + g)(h) for g in _GATES))
        return (jnp.concatenate(w_x, axis=-1), jnp.concatenate(w_h, axis=-1),
                jnp.concatenate(b, axis=-1))


class _LSTM(nn.Module):
    """The LSTM over a whole sequence, time-major (the module's docstring
    says why the input product stands before the loop)."""

    features: int

    @nn.compact
    def __call__(self, x, state: LSTMState, resets):
        # x [T, B, F] float32, resets [T, B] bool (zero the state BEFORE t)
        w_x, w_h, b = _JoinedLSTMCell(self.features, name="cell")(x, state[1])
        with jax.named_scope(device_scopes.LSTM_INPUT):
            # contracts F alone: a batch axis split over a mesh stays split
            z_x = jnp.einsum("tbf,fg->tbg", x, w_x)  # [T, B, 4m]

        def step(carry, xs):
            z_x_t, reset_t = xs  # [B, 4m], [B] bool
            c, h = carry
            keep = (1.0 - reset_t.astype(jnp.float32))[:, None]
            c, h = c * keep, h * keep
            i, f, g, o = jnp.split((jnp.dot(h, w_h) + b) + z_x_t, 4, axis=-1)
            c = nn.sigmoid(f) * c + nn.sigmoid(i) * jnp.tanh(g)
            h = nn.sigmoid(o) * jnp.tanh(c)
            return (c, h), h

        return jax.lax.scan(step, state, (z_x, resets))


@dataclasses.dataclass(frozen=True)
class LSTMCore:
    """`_LSTM` under the name `lstm`: the input product over all T steps, then
    a `lax.scan` over the hidden side of the gates; the state is (c, h)."""

    features: int = 512

    stat_names = ()  # counters the core sows (CORE_STATS), by name
    act_stat_names = ()  # those a fused tick's act step reports beside them

    @property
    def stored_width(self) -> int:
        return self.features

    def initial_state(self, batch: int) -> LSTMState:
        # two buffers: the fused segment donates its carry, and one array
        # donated twice is a runtime error
        return (jnp.zeros((batch, self.features), jnp.float32),
                jnp.zeros((batch, self.features), jnp.float32))

    def to_stored(self, state: LSTMState) -> LSTMState:
        return state

    def from_stored(self, init_c, init_h) -> LSTMState:
        return (init_c, init_h)

    def reset_lanes(self, state: LSTMState, keep) -> LSTMState:
        return zero_lanes(state, keep)

    def __call__(self, x, state: LSTMState, resets):
        x, resets = jnp.moveaxis(x, 1, 0), jnp.moveaxis(resets, 1, 0)  # [T, B, .]
        with jax.named_scope(device_scopes.LSTM_SCAN):
            state, outs = _LSTM(self.features, name="lstm")(x, state, resets)
        return jnp.moveaxis(outs, 0, 1), state


def zero_lanes(state: Any, keep: jnp.ndarray) -> Any:
    """`state` with the lanes where `keep` [B] is 0 back at the initial
    (zero) state; every leaf leads with the lane axis.  A reset for every
    core, and the LSTM's; the trainers call `core.reset_lanes`, which for the
    cores of models/mla_moe.py leaves an attention window's keys and values
    where they are and resets the lane by the slots' validity and the ring's
    head ([B, W] and [B], not a multiply over the window)."""
    kf = keep.astype(jnp.float32)
    return jax.tree.map(
        lambda s: s * kf.reshape((-1,) + (1,) * (s.ndim - 1)), state)


def reduce_stats(collection) -> dict:
    """{counter: scalar} of what the core's layers sowed in one pass: a
    `*_max_*` counter by its largest, a `*_dropped` by its sum, any other by
    its mean over the layers."""
    by_name = {}
    for path, v in jax.tree_util.tree_leaves_with_path(collection):
        name = [k.key for k in path if hasattr(k, "key")][-1]
        by_name.setdefault(name, []).append(v)
    how = lambda n: (jnp.max if "_max_" in n else  # noqa: E731
                     jnp.sum if n.endswith("_dropped") else jnp.mean)
    return {n: how(n)(jnp.stack(v)).astype(jnp.float32)
            for n, v in by_name.items()}


def state_bytes_per_lane(core) -> int:
    shapes = jax.eval_shape(lambda: core.initial_state(1))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# model_type -> (module under models/, its reader of the published keys, its
# core); only the module of the family a file names is imported
FAMILIES = {
    "kimi_linear": ("kimi_linear", "KimiLinearConfig", "KimiLinearCore"),
    "deepseek_v3": ("deepseek_v3", "DeepSeekV3Config", "DeepSeekV3Core"),
    "qwen3_next": ("qwen3_next", "Qwen3NextConfig", "Qwen3NextCore"),
    "ouro": ("ouro", "OuroConfig", "OuroCore"),
    "lfm2_moe": ("lfm2", "Lfm2Config", "Lfm2Core"),
    "laguna": ("laguna", "LagunaConfig", "LagunaCore"),
}


@functools.lru_cache(maxsize=None)
def _load(path: str, compute_dtype: str):
    found = path if os.path.exists(path) else os.path.join(_ROOT, path)
    with open(found) as f:
        cc = json.load(f)
    if cc.get("model_type") not in FAMILIES:
        raise ValueError(
            f"{path}: no core for model_type {cc.get('model_type')!r}")
    module, reader, core = FAMILIES[cc["model_type"]]
    family = importlib.import_module(
        "rainbow_iqn_apex_tpu.models." + module)
    return getattr(family, core)(
        getattr(family, reader).from_dict(cc), jnp.dtype(compute_dtype))


def make_core(cfg):
    """The core `cfg` asks for: `core_config` (a file under configs/cores/,
    found as given or relative to the repository's root), else the LSTM."""
    if cfg.core_config:
        return _load(cfg.core_config, cfg.compute_dtype)
    return LSTMCore(cfg.lstm_size)
