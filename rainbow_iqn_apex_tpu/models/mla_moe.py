"""The blocks the non-LSTM cores of `R2D2Net` run (interface: models/cores.py):
a residual stack in which every layer names its mixer, and whose
feed-forward is a dense SwiGLU in the leading layers (in all of them for a
dense family) and sparse experts in the rest, beside a shared expert where
the family has one.  A block
norms each sub-layer's input (pre-norm) and, where the family says so
(`out_norms`), its output too before the residual add (four norms a block).
The stack is run `passes` times over the SAME layer modules, its final norm
after every pass feeding the next: each layer's leaves stand in the parameter
tree once, a leaf's gradient is the sum over its uses, and the per-lane state
has one entry a (pass, layer), because every use of a layer sees keys of its
own.  The MLA mixer (latent attention over a window of latents) lives here,
and what the mixers share: the attention window (a ring for a lane that acts:
its opening, its mask and its reset), the two published forms of the rotation,
and the short causal convolution with its taps (`_causal_conv`, `_Taps`: the
two delta-rule mixers' and the gated short convolution's).

Six cores are built from them: `models/kimi_linear.py` (three KDA mixers in
four, defined there, and an un-rotated MLA in the fourth),
`models/deepseek_v3.py` (every mixer MLA with decoupled rotary keys, an input
projection in the embedding's place), `models/qwen3_next.py` (three Gated
DeltaNet mixers in four and a gated softmax attention in the fourth, both
defined there; softmax routing and a gated shared expert),
`models/ouro.py` (plain multi-head attention defined there, every
feed-forward dense, four norms a block, the stack run `total_ut_steps`
times), `models/lfm2.py` (a gated short convolution, defined there, in
three layers of four and `models/ouro.py`'s attention with q/k norms in the
fourth; sigmoid routing and NO shared expert) and `models/laguna.py`
(sliding-window and full attention layers side by side, defined there, each
mixer with a span, heads and a rotation of its own, which is why the
rotation by a table of frequencies and the attention by blocks of queries
live here).  Each reads its own published
keys into one `CoreConfig`; which mixer a layer runs, whether the rope
dimensions are rotated, how the router scores, whether the input is
projected, how often the stack is run and whether a block norms its outputs
are read off it.

A mixer is a flax module `Mixer(kc, compute_dtype)` called as
`(x [B, T, hidden], state, seg [B, T]) -> (y, state)` that says under which
name it stands in its layer (`layer_name`), what its per-lane state is at the
start (`zero_state(kc, batch)`: float32, zero = initial, every leaf led by the
lane axis, so models/cores.zero_lanes resets a lane) and, where zeroing every
leaf is more than a reset needs, how a lane is reset (`reset_state(state,
keep)`; `StackCore.reset_lanes` maps the mixers' resets over the (pass,
layer) states).  Six mixers, five kinds of state: a delta-rule matrix with its
convolution's tail (KDA, Gated DeltaNet), a window of latents (MLA), a window
of keys and values (gated attention, plain attention) and, the smallest, the
tail alone of a gated short convolution: the last `conv_kernel` - 1 steps of
its gated input, [B, K-1, hidden] (models/lfm2.py).

MLA's per-lane state, float32, zero = initial: the window's latents `lat`
[B, L, rank + rope] with the rope key UN-rotated, their validity `valid`
[B, L] and the ring's `head` [B].  Where the configuration rotates
(`rope_theta` > 0) the rotation is applied at use, by a slot's position among
the slots attended over (`window_open`'s `pos_k`, the new steps' `pos_q`).  A
score depends on the difference of the two positions alone, so this is the
published rotation by absolute position exactly, with no step counter in the
state and no angle over (W + T) x 1 rad however long a lane runs without a
cut.  An episode cut inside a sequence is a segment boundary: steps interact
only within a segment.

An attention window (MLA's here, the K/V windows of models/qwen3_next.py and
models/ouro.py; all three go through `window_open` and `window_mask`) is as
long as its state's SHAPE says, L slots from 0 to W = `window`, and the shape
alone says which of two things it is.  `window` is the span of the mask (a
query sees at most the last W slots, itself included) and the length a window
grows to.
  Fewer than W slots: a sequence the learner unrolls.  It starts from none
  (`from_stored`), stands in position order with head 0 and grows by
  `[window; new]`, 0 -> burn-in -> min(burn-in + T, W): no slot is projected,
  rotated or scored that no step wrote, and a slot's position is the step's
  position in the sequence.
  W slots: a RING, what a lane that acts holds from the start
  (`initial_state`).  Slot s holds the step of age-ordered position
  (s - head) mod W: the slot at `head` is the oldest and the next to be
  written.  A tick (T = 1) writes its step over that slot FIRST, in place,
  and attends over the ring's W slots: the slot it overwrote is the one of
  `[window; new]` that the mask shut out, so the scores are [.., 1, W], the
  same numbers, and a tick touches one slot of the window where a roll
  rewrote all of them.  A call of T > 1 steps on a ring (an eval rollout)
  attends over `[ring; new]`, the ring's slots at their ages and the new
  steps at W + i, and then writes its last min(T, W) steps, step i to slot
  (head + i) mod W.  Either way `head` advances by T mod W and the ring holds
  the last W slots of `[window; new]`.  A window that has grown to W slots
  (position order, head 0) is a ring already.
A slot whose `valid` is 0 weighs exactly 0 (its score is `NEG`, whose softmax
weight is 0.0 in float32, and what a slot holds is finite), so leaving it out
is the same result, and so a lane is reset by its slots' validity and its
head alone (`window_reset`: [B, W] and [B], what the slots held stays in them,
stale); `zero_lanes` on a ring stays a correct reset, everything zero being
the initial state.  A call on a ring sows `attn_act_window_written_share`,
the slots written over the slots held: 1 / W on a tick.

The expert layer is told which experts it holds (`experts_here` from
`first_expert`): it routes over all of them, sorts the assignments that fell
on its own by expert and runs one grouped (ragged) product per projection.
The scores are sigmoids or a softmax over all the experts (`route`), the
chosen ones' weights their scores over their sum, times `route_scale`; the
shared expert is added as it is or weighed by a sigmoid gate (`shared_gate`),
and a family without one says `shared_width` 0: the layer then holds no
`shared` leaf and runs no `moe_shared` op, and its output is the held
experts' part alone.
No capacity: the row buffer is chosen, by the count of held assignments, as
the smallest rung of a ladder that holds them (`EXPERT_ROWS`): no rows at all
(the held part is zeros: nothing is gathered, multiplied or scattered), the
token count, and on top the rows that hold every assignment, so no token is
ever dropped.  The token count, because that is what a layer holds wherever
its router chooses alike for all its tokens and one of a token's choices is
held; a layer that holds more takes the top rung.  A rung with rows is a
branch of a `switch`, and what a branch costs is paid whichever branch runs:
under a gradient a `switch` hands the backward pass what EVERY branch saved
(its gathered rows, its products, its mask), made and zero-filled for the
branches not taken, and a branch is 3 to 90 MB of program at the published
widths, which a chip's peak memory counts.  So the ladder has two rungs with
rows and not one a likely count (a third made the Kimi-Linear core's program
half a gigabyte larger on the chip: PERF.md, PR 49), and the top rung, whose
rows are `top_k` times the other's and which runs where a router spreads,
keeps nothing for the backward pass: it makes its rows again there from the
layer's input, sort and kernels (`jax.checkpoint`), a third forward where it
runs and no buffer where it does not.  The rung at the token count keeps
what it computed, as any layer under the stack's `nn.remat` does.  Where the
layer has few tokens (`FEW_ROWS`: the actor's 16 lanes, an eval rollout, a
tiny sequence pass) it sorts nothing: it walks the held experts, skips every
one no token chose, and runs one that has rows on all the tokens from that
expert's own kernels, each token weighed by its routing weight for it (0.0
where it did not choose it); the same products on the same operands, summed
in another order, and what the walk costs goes by the experts touched
(`moe_act_touched_expert_share`), not by the experts held.  What absent
experts would add is left out (the chip's share of an expert-parallel layer;
tests/test_kimi_linear_core.py, tests/test_deepseek_v3_core.py and
tests/test_lfm2_core.py add the shares up to the uncut layer).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from rainbow_iqn_apex_tpu.models.cores import CORE_STATS as STATS
from rainbow_iqn_apex_tpu.models.cores import zero_lanes
from rainbow_iqn_apex_tpu.obs import device_scopes

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
# the rungs of the grouped product's row buffer, as multiples of the token
# count, under the one that holds every assignment (top_k times it): none,
# and the token count, which is what a layer holds where its router chooses
# alike for all its tokens and one of a token's choices is held (what a
# seeded selection bias deals, and what an untrained router collapses to
# within a few learn steps).  Two rungs with rows and no more: each is a
# branch whose saved rows are made on every learn step and whose program, up
# to 90 MB at the published widths, a chip's peak memory counts
EXPERT_ROWS = (0.0, 1.0)
# up to so many rows (tokens x the choices that can be held) the expert layer
# sorts nothing and walks the held experts its tokens chose
FEW_ROWS = 1024


@dataclasses.dataclass(frozen=True)
class CoreConfig:
    """What the stack is built from; a family's reader fills it from its own
    published keys (`KimiLinearConfig`, `DeepSeekV3Config`,
    `Qwen3NextConfig`, `OuroConfig`, `Lfm2Config`, `LagunaConfig`).  A mixer's sizes are
    read by that mixer alone and the expert layer's by `_MoE` alone, so a
    family leaves the others' at their zeros (a dense family has no expert
    layer: `first_dense` is all its layers)."""

    hidden: int
    mixers: Tuple[Any, ...]  # the mixer module of each layer, in order
    eps: float
    passes: int = 1  # how many times the stack is run over the same weights
    out_norms: bool = False  # a block norms its sub-layers' outputs too
    first_dense: int = 0  # the leading layers whose feed-forward is dense
    dense_width: int = 0
    # the expert layer (`_MoE`)
    experts: int = 0
    top_k: int = 0
    expert_width: int = 0
    shared_width: int = 0  # 0: the expert layer has no shared expert
    experts_here: int = 0
    first_expert: int = 0
    route: str = "sigmoid"  # the router's scores: "sigmoid" or "softmax"
    route_scale: float = 1.0
    shared_gate: bool = False  # the shared expert weighed by a sigmoid gate
    in_proj: bool = False  # a projection of the trunk's features to `hidden`
    window: int = 0  # an attention mask's span, and the slots a window grows to
    rope_theta: float = 0.0  # 0: the rope dimensions are not rotated (NoPE)
    # MLA (`_MLA`, here)
    mla_heads: int = 0
    nope: int = 0
    rope: int = 0
    v_dim: int = 0
    kv_rank: int = 0
    # the short convolution's taps (the delta-rule mixers', and the gated
    # short convolution's of models/lfm2.py, whose channels are `hidden`);
    # the chunked scan of the delta-rule mixers, then KDA's sizes
    # (models/kimi_linear.py) and Gated DeltaNet's (models/qwen3_next.py)
    conv_kernel: int = 0
    chunk: int = 0
    block: int = 0
    kda_heads: int = 0
    kda_dim: int = 0
    low_rank: int = 0
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    # softmax attention over a K/V window (models/qwen3_next.py gated and
    # partly rotated, models/ouro.py plain and fully rotated)
    attn_heads: int = 0
    attn_kv_heads: int = 0
    attn_head_dim: int = 0
    attn_rotary_dim: int = 0
    attn_qk_norm: bool = False  # plain attention norms q and k a head

    @property
    def layers(self) -> int:
        return len(self.mixers)


def _mm(eq: str, a, b, dtype):
    """einsum on `dtype` operands, float32 accumulation."""
    return jnp.einsum(
        eq, a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.float32,
        precision=HI if dtype == jnp.float32 else None)


class _Linear(nn.Module):
    features: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        return _mm("...i,io->...o", x, kernel, self.compute_dtype)


class _RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * scale


class _SwiGLU(nn.Module):
    width: int
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        lin = lambda n, name: _Linear(n, self.compute_dtype, name=name)  # noqa: E731
        h = jax.nn.silu(lin(self.width, "gate")(x)) * lin(self.width, "up")(x)
        return lin(x.shape[-1], "down")(h)


class _Taps(nn.Module):
    kernel: int
    channels: int

    @nn.compact
    def __call__(self):
        bound = 1.0 / math.sqrt(self.kernel)
        return self.param(
            "taps", lambda k, s: jax.random.uniform(k, s, jnp.float32,
                                                    -bound, bound),
            (self.kernel, self.channels))


def _causal_conv(z, taps, tail, seg):
    """Causal depthwise convolution of z [B, T, C] after the last K-1 steps
    `tail` [B, K-1, C] of the lane's past, over the steps of a step's own
    segment: out_t = sum_j taps[j] z_{t-j}.  Returns (out, the new tail)."""
    b, t, _ = z.shape
    kk = taps.shape[0]
    zin = jnp.concatenate([tail, z], axis=1)  # [B, K-1+T, C]
    sin = jnp.concatenate([jnp.zeros((b, kk - 1), seg.dtype), seg], axis=1)
    conv = sum(
        taps[j] * zin[:, kk - 1 - j: kk - 1 - j + t]
        * (sin[:, kk - 1 - j: kk - 1 - j + t] == seg)[..., None]
        for j in range(kk))
    return conv, zin[:, t:] * (sin[:, t:] == seg[:, -1:])[..., None]


# ------------------------------------------------------------------- MLA
def _cos_sin(u, pos, freq):
    """(cos, sin) of the angles pos x freq [n], shaped to broadcast against
    u [B, S, ..., n]; pos [S], or [B, S] where the lanes' slots stand at
    different positions (a ring)."""
    pos = jnp.atleast_2d(pos).astype(jnp.float32)
    angle = (pos[..., None] * freq).reshape(  # [B or 1, S, n]
        pos.shape + (1,) * (u.ndim - 3) + freq.shape)
    return jnp.cos(angle), jnp.sin(angle)


def rope_cos_sin(u, pos, theta: float):
    """(cos, sin) of the angles pos x theta^(-2i/d), i < d/2, shaped to
    broadcast against u [B, S, ..., d/2]."""
    d = u.shape[-1]
    return _cos_sin(
        u, pos, theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def rotate_pairs(u, pos, theta: float):
    """u [B, S, ..., d] with its adjacent pairs (u_2i, u_2i+1) turned by
    pos x theta^(-2i/d): the published `rope_interleave` rotation (which
    permutes to halves and applies `rotate_half`: the same scores)."""
    d = u.shape[-1]
    cos, sin = rope_cos_sin(u, pos, theta)
    pairs = u.reshape(*u.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(u.shape)


def rotate_halves(u, pos, theta: float):
    """u [B, S, ..., d] turned by pos: (u_i, u_{i + d/2}) by the angle
    pos x theta^(-2i/d), the published `rotate_half` form."""
    d = u.shape[-1]
    cos, sin = rope_cos_sin(u, pos, theta)
    a, b = u[..., : d // 2], u[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@dataclasses.dataclass(frozen=True)
class Rotation:
    """A rotation as a table: pair i of a head turns by pos x `freq[i]`, and
    cos and sin are multiplied by `factor` (a scaled rotation's attention
    factor; 1.0 for a plain one).  The table's 2 x len(freq) leading
    dimensions of a head are turned, the rest passed through.  A score of two
    heads so turned depends on the difference of their positions alone,
    whatever the table, so the rotation at use by a slot's position (the
    module's docstring) holds for it as for `theta^(-2i/d)`."""

    freq: Tuple[float, ...]
    factor: float = 1.0


def rotate_table(u, pos, rot: Rotation):
    """u [B, S, ..., d] with its first n = 2 len(rot.freq) dimensions turned
    by pos in the `rotate_half` form over those n, (u_i, u_{i + n/2}) by
    pos x freq[i], and times `rot.factor`; dimensions from n on as they
    are."""
    n = 2 * len(rot.freq)
    cos, sin = _cos_sin(u, pos, jnp.asarray(rot.freq, jnp.float32))
    cos, sin = cos * rot.factor, sin * rot.factor
    a, b = u[..., : n // 2], u[..., n // 2: n]
    rest = [u[..., n:]] if n < u.shape[-1] else []
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, *rest], axis=-1)


# ------------------------------------------------------ attention windows
def window_zero_state(batch: int, slots: int, **payload):
    """The empty window of an attention mixer: `payload` names what a slot
    keeps and its shape ([B, slots, *shape], float32), beside the slots'
    validity [B, slots] and the ring's head [B]."""
    return {**{name: jnp.zeros((batch, slots) + tuple(shape), jnp.float32)
               for name, shape in payload.items()},
            "valid": jnp.zeros((batch, slots), jnp.float32),
            "head": jnp.zeros((batch,), jnp.float32)}


def kv_window_zero_state(kc: CoreConfig, batch: int):
    """The empty window of an attention mixer that keeps keys and values:
    [B, W, Hkv, d] each."""
    kv = (kc.attn_kv_heads, kc.attn_head_dim)
    return window_zero_state(batch, kc.window, k=kv, v=kv)


def window_reset(state, keep):
    """A window with the lanes where `keep` [B] is 0 back at the start: by the
    slots' validity and the head alone.  What the slots held stays where it
    is and weighs exactly 0 (the module's docstring)."""
    kf = keep.astype(jnp.float32)
    return {**state, "valid": state["valid"] * kf[:, None],
            "head": state["head"] * kf}


def window_keep(n: int, t: int, w: int) -> int:
    """The first slot of `[window; new]` (n + t slots) that is handed on: the
    last min(n + t, w) stay.  A full window (n = w) drops t."""
    return max(n + t - w, 0)


def ring_positions(head, w: int):
    """[B, W]: the age-ordered position of every slot of a ring whose oldest
    slot is `head` [B]."""
    return (jnp.arange(w)[None] - head.astype(jnp.int32)[:, None]) % w


class Window(NamedTuple):
    """What `window_open` hands a mixer: the slots its new steps attend over
    and the state it hands on."""

    held: Dict[str, Any]  # name -> [B, S, ...]
    pos_q: Any  # [T]: the new steps' positions
    pos_k: Any  # [S], or [B, S] on a ring: the held slots' positions
    valid: Any  # [B, S]
    seg: Any  # [B, S] (or [B, 1]): the held slots' segment ids, a window's 0
    state: Dict[str, Any]


def window_open(state, new, seg, w: int) -> Window:
    """The new steps `new` (name -> [B, T, ...]) of segment ids `seg` [B, T]
    taken into a window of span `w`; the state's SHAPE says how.

    Fewer than `w` slots (a sequence's window, in position order, head 0):
    the steps attend over `[window; new]` and its last min(L + T, w) slots
    are handed on.  `w` slots (a ring): one step is written FIRST, over the
    oldest slot, which its query may not see anyway, and attends over the
    ring's `w` slots; several attend over `[ring; new]` and the last
    min(T, w) are written then, step i to slot (head + i) mod w: the last `w`
    slots of `[window; new]` in age order, in place."""
    valid, head = state["valid"], state["head"]
    b, n = valid.shape
    t = seg.shape[1]
    last = seg[:, -1:]
    if n == w:  # a ring: the last m steps go to the slots from head + t - m on
        m = min(t, w)
        lanes = jnp.arange(b)[:, None]
        slots = (head.astype(jnp.int32)[:, None] + jnp.arange(t - m, t)) % w
        put = lambda buf, x: buf.at[lanes, slots].set(  # noqa: E731
            x[:, -m:], unique_indices=True)
        head = (head + t) % w
    if n == w and t == 1:
        held = {name: put(state[name], x) for name, x in new.items()}
        valid = put(valid * (last == 0), jnp.ones((b, 1), jnp.float32))
        return Window(held, jnp.full((1,), w - 1), ring_positions(head, w),
                      valid, seg, {**held, "valid": valid, "head": head})
    held = {name: jnp.concatenate([state[name], x], axis=1)
            for name, x in new.items()}
    seg_all = jnp.concatenate([jnp.zeros((b, n), seg.dtype), seg], axis=1)
    valid_all = jnp.concatenate([valid, jnp.ones((b, t), jnp.float32)], axis=1)
    pos_q = n + jnp.arange(t)
    after = valid_all * (seg_all == last)  # an ended segment's slots are void
    if n < w:
        keep = window_keep(n, t, w)
        return Window(held, pos_q, jnp.arange(n + t), valid_all, seg_all,
                      {**{name: x[:, keep:] for name, x in held.items()},
                       "valid": after[:, keep:], "head": head})
    pos_k = jnp.concatenate(
        [ring_positions(state["head"], w), jnp.broadcast_to(pos_q, (b, t))],
        axis=1)
    return Window(held, pos_q, pos_k, valid_all, seg_all,
                  {**{name: put(state[name], x) for name, x in new.items()},
                   "valid": put(after[:, :n], after), "head": head})


def window_mask(win: Window, seg, w: int):
    """[B, T, S]: what a query of the new steps `seg` [B, T] may attend to
    among the slots `win` holds: causal, at most the last `w` slots the step
    itself included, valid, of the step's own segment."""
    pos_q = win.pos_q[:, None]
    pos_k = jnp.atleast_2d(win.pos_k)[:, None, :]  # [B or 1, 1, S]
    return (pos_k <= pos_q) & (pos_k > pos_q - w) & (
        win.valid[:, None, :] > 0) & (win.seg[:, None, :] == seg[:, :, None])


def sow_written_share(module, state, t: int, w: int):
    """`attn_act_window_written_share` of a call on a ring (none on a window
    that still grows): the slots it writes over the slots it holds."""
    if state["valid"].shape[1] == w:
        module.sow(STATS, "attn_act_window_written_share", min(t, w) / w)


# queries a block of `attend_by_blocks`: the lanes' width, and at a span of
# 512 a block's band is 639 of the 1,023 slots held
ATTN_BLOCK = 128


def attend(q, k, v, mask, dtype):
    """Softmax attention of grouped queries q [B, T, G, R, d] (R query heads
    a key/value head) over k, v [B, S, G, d] under mask [B, T, S]: o [B, T,
    G, R, d].  A masked score is `NEG`, whose weight is exactly 0."""
    scores = _mm("btgrd,bsgd->bgrts", q, k, dtype)
    scores = jnp.where(
        mask[:, None, None], scores / math.sqrt(q.shape[-1]), NEG)
    return _mm("bgrts,bsgd->btgrd", jax.nn.softmax(scores, axis=-1), v, dtype)


def attend_by_blocks(q, k, v, valid, seg_k, seg_q, span: int, dtype,
                     block: int = 0):
    """`attend` of T new steps over S = n + T slots IN POSITION ORDER (slot j
    at position j, the query of new step i at n + i; k, v, valid [B, S] and
    seg_k [B, S] the slots', seg_q [B, T] the steps'), a block of `block`
    queries at a time, each over the slots of its own band alone: a query at
    position p sees the slots (p - span, p], so the block of queries
    [p0, p1) scores the slots [max(p0 - span + 1, 0), p1) and no others, and
    a span as long as the slots held cuts nothing but what lies after the
    block: at a span of half the slots a block of 128 scores 62% of the
    columns the dense form would.  A block's scores [B, G, R, block, band]
    are made again on the way back (`jax.checkpoint` a block), so neither
    pass keeps a score array of the whole sequence ([8, 64, 512, 1024]
    float32 is 1.07 GB on the learn path, and the dense form's backward
    holds several).  Returns (o [B, T, G, R, d], the mask's live entries, a
    float32 count, the columns computed, an int; `block` 0 is `ATTN_BLOCK`).
    A key outside a block's band is masked in the dense form, weighs exactly
    0 there, and is left out here: the same numbers summed over fewer
    zeros."""
    t, s = q.shape[1], k.shape[1]
    n, block = s - t, block or ATTN_BLOCK
    one_block = jax.checkpoint(
        lambda qb, kb, vb, mb: attend(qb, kb, vb, mb, dtype))
    outs, live, computed = [], 0.0, 0
    for i0 in range(0, t, block):
        i1 = min(i0 + block, t)
        lo, hi = max(n + i0 - span + 1, 0), n + i1
        pos_q = (n + jnp.arange(i0, i1))[:, None]
        pos_k = jnp.arange(lo, hi)[None]
        mask = ((pos_k <= pos_q) & (pos_k > pos_q - span))[None] & (
            valid[:, None, lo:hi] > 0) & (
                seg_k[:, None, lo:hi] == seg_q[:, i0:i1, None])
        outs.append(one_block(q[:, i0:i1], k[:, lo:hi], v[:, lo:hi], mask))
        live = live + jnp.sum(mask, dtype=jnp.float32)
        computed += (i1 - i0) * (hi - lo)
    return jnp.concatenate(outs, axis=1), live, computed


def ring_in_age_order(win: Window, head, w: int):
    """What several steps on a ring attend over, `[ring; new]` (`window_open`
    of T > 1 steps on `w` slots whose oldest was `head` [B]), with the ring's
    slots turned into age order: (held, valid), every slot j then at position
    j as in a sequence's window.  One gather of `w` slots a leaf."""
    order = (head.astype(jnp.int32)[:, None] + jnp.arange(w)) % w
    lanes = jnp.arange(order.shape[0])[:, None]
    turn = lambda x: jnp.concatenate(  # noqa: E731
        [x[:, :w][lanes, order], x[:, w:]], axis=1)
    return {name: turn(x) for name, x in win.held.items()}, turn(win.valid)


class _MLA(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    layer_name = "mla"

    reset_state = staticmethod(window_reset)

    @staticmethod
    def zero_state(kc: CoreConfig, batch: int):
        return window_zero_state(batch, kc.window,
                                 lat=(kc.kv_rank + kc.rope,))

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype
        b, t, _ = x.shape
        h, w, rank = kc.mla_heads, kc.window, kc.kv_rank
        with jax.named_scope(device_scopes.MLA_PROJ):
            q = _Linear(h * (kc.nope + kc.rope), cd, name="q_proj")(x)
            q = q.reshape(b, t, h, kc.nope + kc.rope)
            kva = _Linear(rank + kc.rope, cd, name="kv_a")(x)
            lat = jnp.concatenate(
                [_RMSNorm(kc.eps, name="kv_norm")(kva[..., :rank]),
                 kva[..., rank:]], axis=-1)
        win = window_open(state, {"lat": lat}, seg, w)
        lat = win.held["lat"]  # [B, S, rank + rope]
        with jax.named_scope(device_scopes.MLA_PROJ):
            kv = _Linear(h * (kc.nope + kc.v_dim), cd, name="kv_b")(
                lat[..., :rank]).reshape(
                    b, lat.shape[1], h, kc.nope + kc.v_dim)

        def rope(u, pos):
            if not kc.rope_theta:
                return u
            with jax.named_scope(device_scopes.MLA_ROPE):
                return rotate_pairs(u, pos, kc.rope_theta)

        with jax.named_scope(device_scopes.MLA_ATTN):
            scores = (_mm("bthd,bshd->bhts", q[..., : kc.nope],
                          kv[..., : kc.nope], cd)
                      + _mm("bthr,bsr->bhts",
                            rope(q[..., kc.nope:], win.pos_q),
                            rope(lat[..., rank:], win.pos_k), cd))
            mask = window_mask(win, seg, w)
            scores = jnp.where(
                mask[:, None], scores / math.sqrt(kc.nope + kc.rope), NEG)
            o = _mm("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1),
                    kv[..., kc.nope:], cd)
        with jax.named_scope(device_scopes.MLA_PROJ):
            y = _Linear(kc.hidden, cd, name="o_proj")(
                o.reshape(b, t, h * kc.v_dim))
        self.sow(STATS, "mla_live_key_share", jnp.mean(mask, dtype=jnp.float32))
        sow_written_share(self, state, t, w)
        return y, win.state


# ---------------------------------------------------------- expert layer
class _Router(nn.Module):
    experts: int
    scores: str = "sigmoid"  # or "softmax" over all the experts

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.experts), jnp.float32)
        bias = self.param("select_bias", nn.initializers.zeros,
                          (self.experts,), jnp.float32)
        # float32 throughout: the choice of experts is discrete
        logits = jnp.dot(x, kernel, precision=HI)
        if self.scores == "softmax":
            return jax.nn.softmax(logits, axis=-1), bias
        return jax.nn.sigmoid(logits), bias


def _stacked_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) / math.sqrt(shape[1])


class _ExpertWeights(nn.Module):
    """The held experts' stacked kernels (gate, up, down)."""

    count: int
    width: int
    hidden: int

    @nn.compact
    def __call__(self):
        n, f, w = self.count, self.hidden, self.width
        return (self.param("gate", _stacked_init, (n, f, w)),
                self.param("up", _stacked_init, (n, f, w)),
                self.param("down", _stacked_init, (n, w, f)))


def _grouped_swiglu(weights, xs, group_sizes, dtype):
    """SwiGLU of rows sorted by expert: one ragged product a projection."""
    gate, up, down = weights
    rd = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
        a.astype(dtype), w.astype(dtype), group_sizes,
        preferred_element_type=jnp.float32,
        precision=HI if dtype == jnp.float32 else None)
    return rd(jax.nn.silu(rd(xs, gate)) * rd(xs, up), down)


def _walked_swiglu(weights, x, coef, group_sizes, dtype):
    """The held experts' part for a few tokens x [n, f]: a walk over the held
    experts that skips every one no token chose.  One that has rows slices
    its own three kernels out of the stacks INSIDE the branch taken, so the
    slice alone is read and cast (the TPU compiler folds slice and cast into
    the product's operand), and runs the SwiGLU on all n tokens, each weighed
    by `coef[e]` [n]: its routing weight for that expert, 0.0 for a token that
    did not choose it, which adds exactly 0.  Cost goes by the experts
    touched, not by the experts held.  The walk is unrolled with static
    slices: from a loop over the slots the compiler hoists the cast of the
    WHOLE stacks (PERF.md, PR 44)."""
    xc = x.astype(dtype)
    y = jnp.zeros_like(x)
    for e in range(coef.shape[0]):
        def run(y, e=e):
            gate, up, down = (w[e] for w in weights)
            h = jax.nn.silu(_mm("ni,io->no", xc, gate, dtype)) * _mm(
                "ni,io->no", xc, up, dtype)
            return y + _mm("ni,io->no", h, down, dtype) * coef[e][:, None]

        y = jax.lax.cond(group_sizes[e] > 0, run, lambda y: y, y)
    return y


class _MoE(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    @nn.compact
    def __call__(self, x):
        kc = self.kc
        lead, f = x.shape[:-1], x.shape[-1]
        x = x.reshape(-1, f)
        n, k, held_n = x.shape[0], kc.top_k, kc.experts_here
        most = n * min(k, held_n)  # rows that hold every assignment
        few = most <= FEW_ROWS  # a few tokens (the actor): no rows are sorted
        with jax.named_scope(device_scopes.MOE_ROUTE):
            s, bias = _Router(kc.experts, kc.route, name="router")(x)
            _, idx = jax.lax.top_k(s + bias, k)
            sel = jnp.take_along_axis(s, idx, axis=-1)
            w = sel / sel.sum(axis=-1, keepdims=True) * kc.route_scale
            local = idx - kc.first_expert
            held = (local >= 0) & (local < held_n)
            if few:
                # [held_n, n, k]: which of a token's choices fell on expert e
                # (at most one: top_k's choices are distinct)
                chose = local[None] == jnp.arange(held_n)[:, None, None]
                coef = jnp.sum(jnp.where(chose, w[None], 0.0), axis=-1)
                group_sizes = jnp.sum(chose, axis=(1, 2))
                n_held = group_sizes.sum()
            else:
                key = jnp.where(held, local, held_n).reshape(-1)
                order = jnp.argsort(key, stable=True)  # held first, by expert
                group_sizes = jnp.bincount(key, length=held_n + 1)[:held_n]
                n_held = group_sizes.sum()
                w_sorted = (w * held).reshape(-1)[order]
        weights = _ExpertWeights(held_n, kc.expert_width, f,
                                 name="experts")()
        cd = self.compute_dtype

        def with_rows(rows):
            if not rows:  # nothing held: no row gathered, multiplied or added
                return lambda weights, x, *_: jnp.zeros_like(x)

            def run(weights, x, order, w_sorted, group_sizes, n_held):
                tok = order[:rows] // k
                # rows past the held assignments belong to no group: the
                # grouped product leaves them (and, backwards, their input's
                # gradient) unwritten, so both sides are masked
                live = (jnp.arange(rows) < n_held)[:, None]
                xs = jnp.where(live, x.astype(cd)[tok], 0)
                ys = _grouped_swiglu(
                    weights, xs, group_sizes.astype(jnp.int32), cd)
                ys = jnp.where(live, ys * w_sorted[:rows, None], 0.0)
                return jnp.zeros_like(x).at[tok].add(ys)
            # the top rung saves nothing for the backward pass and makes its
            # rows again there, from the `switch`'s own operands: its rows
            # are top_k times the token count, and a `switch` under a
            # gradient makes and fills what every branch saved on every learn
            # step, whichever ran
            return jax.checkpoint(run) if rows == most else run

        with jax.named_scope(device_scopes.MOE_EXPERTS):
            if few:
                y = _walked_swiglu(weights, x, coef, group_sizes, cd)
            else:
                sizes = sorted(
                    {min(most, int(n * r)) for r in EXPERT_ROWS} | {most})
                pick = sum((n_held > r).astype(jnp.int32) for r in sizes[:-1])
                y = jax.lax.switch(pick, [with_rows(r) for r in sizes],
                                   weights, x, order, w_sorted, group_sizes,
                                   n_held)
        if kc.shared_width:  # 0: a family without a shared expert
            with jax.named_scope(device_scopes.MOE_SHARED):
                shared = _SwiGLU(kc.shared_width, cd, name="shared")(x)
                if kc.shared_gate:
                    shared = shared * jax.nn.sigmoid(
                        _Linear(1, cd, name="shared_gate")(x))
                y = y + shared
        load = jnp.bincount(idx.reshape(-1), length=kc.experts)
        if few:  # nothing is buffered: the rows that hold every assignment
            rows_taken = most
            fill = n_held / most
            self.sow(STATS, "moe_act_touched_expert_share",
                     jnp.mean(group_sizes > 0, dtype=jnp.float32))
        else:
            rows_taken = jnp.asarray(sizes, jnp.int32)[pick]
            # the empty rung masks no row: 1.0 there, not 0 / 0
            fill = jnp.where(rows_taken > 0,
                             n_held / jnp.maximum(rows_taken, 1), 1.0)
        self.sow(STATS, "moe_held_assign_share", n_held / (n * k))
        self.sow(STATS, "moe_expert_load_max_over_mean",
                 load.max() / (n * k / kc.experts))
        self.sow(STATS, "moe_tokens_dropped",
                 (n_held - jnp.minimum(n_held, rows_taken)).astype(jnp.float32))
        # the share of the taken buffer's rows that hold an assignment: the
        # rest of the gather, the products' rows and the scatter is masked
        self.sow(STATS, "moe_row_fill_share", fill)
        return y.reshape(*lead, f)


# ----------------------------------------------------------------- stack
def state_key(kc: CoreConfig, r: int, i: int) -> str:
    """Where the per-lane state keeps layer i's r-th use (both 1-based): by
    the layer where the stack is run once, by pass and layer where it is run
    several times."""
    return f"layer_{i}" if kc.passes == 1 else f"pass_{r}_layer_{i}"


class _Layer(nn.Module):
    kc: CoreConfig
    index: int  # 1-based, as linear_attn_config counts
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, state, seg):
        kc, cd = self.kc, self.compute_dtype

        def norm(name, u):
            with jax.named_scope(device_scopes.CORE_NORM):
                return _RMSNorm(kc.eps, name=name)(u)

        mixer = kc.mixers[self.index - 1]
        y, state = mixer(kc, cd, name=mixer.layer_name)(
            norm("mix_norm", x), state, seg)
        x = x + (norm("mix_out_norm", y) if kc.out_norms else y)
        hn = norm("ffn_norm", x)
        if self.index <= kc.first_dense:
            with jax.named_scope(device_scopes.DENSE_FFN):
                y = _SwiGLU(kc.dense_width, cd, name="ffn")(hn)
        else:
            y = _MoE(kc, cd, name="moe")(hn)
        return x + (norm("ffn_out_norm", y) if kc.out_norms else y), state


class _Stack(nn.Module):
    kc: CoreConfig
    compute_dtype: Any

    @nn.compact
    def __call__(self, x, state, resets):
        kc = self.kc
        if kc.in_proj:  # where a language model has its embedding
            with jax.named_scope(device_scopes.CORE_EMBED):
                x = _Linear(kc.hidden, self.compute_dtype, name="in_proj")(x)
        elif x.shape[-1] != kc.hidden:
            raise ValueError(
                f"the core's hidden size is {kc.hidden} and the trunk feeds "
                f"it {x.shape[-1]} features: this configuration has no "
                f"projection between them (80x80 frames give 2,304)")
        seg = jnp.cumsum(resets.astype(jnp.int32), axis=1)
        # every layer module is built once and called once a pass: a second
        # call of a flax module reads the leaves the first one made
        layers = [nn.remat(_Layer)(kc, i, self.compute_dtype,
                                   name=f"layer_{i}")
                  for i in range(1, kc.layers + 1)]
        final_norm = _RMSNorm(kc.eps, name="final_norm")
        new_state = {}

        def one_pass(x, r):
            for i, layer in enumerate(layers, 1):
                key = state_key(kc, r, i)
                with jax.named_scope(device_scopes.CORE_LAYER):
                    x, new_state[key] = layer(x, state[key], seg)
            with jax.named_scope(device_scopes.CORE_NORM):
                return final_norm(x)

        if kc.passes == 1:  # no pass to tell from another: the paths stay
            return one_pass(x, 1), new_state
        for r in range(1, kc.passes + 1):
            with jax.named_scope(device_scopes.LOOP_PASS):
                x = one_pass(x, r)
        self.sow(STATS, "loop_passes", float(kc.passes))  # the weights' uses
        return x, new_state


def _uses(kc: CoreConfig):
    """(state key, mixer) of every use of a layer, pass by pass."""
    return [(state_key(kc, r, i), mixer) for r in range(1, kc.passes + 1)
            for i, mixer in enumerate(kc.mixers, 1)]


def _reset_of(mixer):
    """How a mixer's state is reset: its `reset_state(state, keep)` beside its
    `zero_state`, else the multiply by `keep` of every leaf."""
    return getattr(mixer, "reset_state", zero_lanes)


class StackCore:
    """The core interface (models/cores.py) over `_Stack`: zero start state,
    nothing stored in the ring.  A family's core (`KimiLinearCore`,
    `DeepSeekV3Core`, `Qwen3NextCore`, `OuroCore`, `Lfm2Core`, `LagunaCore`)
    is a frozen dataclass of `kc` and `compute_dtype` that names the counters
    it reports,
    `stat_names`: each is an output of the compiled segment, so a core lists
    what its cell reads (`moe_stat_names` where it has expert layers)."""

    stored_width = 0  # zero start state: the ring stores no state
    moe_stat_names = ("moe_expert_load_max_over_mean", "moe_held_assign_share",
                      "moe_tokens_dropped")

    @property
    def act_stat_names(self):
        """What a fused tick's act step reports of its own, after the learn
        steps' `stat_names` in the segment's outputs: the share of the held
        experts the lanes' tokens touched, where the stack has expert layers,
        and the share of its windows' slots a tick writes, where it has
        attention windows."""
        names = ()
        if self.kc.first_dense < self.kc.layers:
            names += ("moe_act_touched_expert_share",)
        if any(_reset_of(m) is window_reset for m in self.kc.mixers):
            names += ("attn_act_window_written_share",)
        return names

    def reset_lanes(self, state, keep):
        """`state` with the lanes where `keep` [B] is 0 back at the start,
        each (pass, layer) state as its mixer says (`reset_state`): an
        attention window by its validity, any other by `zero_lanes`."""
        return {key: _reset_of(mixer)(state[key], keep)
                for key, mixer in _uses(self.kc)}

    def _zero_state(self, kc: CoreConfig, batch: int):
        return {key: mixer.zero_state(kc, batch) for key, mixer in _uses(kc)}

    def initial_state(self, batch: int):
        """One entry a (pass, layer): every use of a layer has a state of its
        own, each leaf led by the lane axis.  The attention windows hold
        `window` slots, none valid: what a lane that acts starts from."""
        return self._zero_state(self.kc, batch)

    def to_stored(self, state):
        b = jax.tree.leaves(state)[0].shape[0]
        return (jnp.zeros((b, 0), jnp.float32), jnp.zeros((b, 0), jnp.float32))

    def from_stored(self, init_c, init_h):
        """A sequence's start state: `initial_state` with every attention
        window at zero slots (the mixers grow it, the module's docstring says
        how), so an unroll never works on slots no step of it wrote."""
        return self._zero_state(
            dataclasses.replace(self.kc, window=0), init_c.shape[0])

    def __call__(self, x, state, resets):
        return _Stack(self.kc, self.compute_dtype, name="core")(
            x, state, resets)
