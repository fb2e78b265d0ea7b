"""The Qwen3-Next core (models/qwen3_next.py over models/mla_moe.py and the
delta-rule recurrence of models/kimi_linear.py) against its plain float32
reference (tests/reference_qwen3_next_core.py), at tiny widths (value heads
twice the key heads, two query heads a key/value head, half of each head
rotated), float32 compute, seeded weights: what is this family's own (the
cases every family shares are tests/test_core_reference.py's); and the two
other cores, which run the same stack, held to the parameter trees they had
before a layer named its mixer."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import kimi_linear as kl
from rainbow_iqn_apex_tpu.models import qwen3_next as qn
from rainbow_iqn_apex_tpu.models.cores import (
    reduce_stats,
    state_bytes_per_lane,
)

import core_families as cf
import reference_qwen3_next_core as ref
from core_families import close

FAMILY = "qwen3_next"
FEATURES = cf.FAMILIES[FAMILY].features
tiny_cc = functools.partial(cf.tiny_cc, FAMILY)
make = functools.partial(cf.make, FAMILY)
jitted = functools.partial(cf.jitted, FAMILY)

SHORT = dict(full_attention_interval=2, layers_here=2)  # one layer of each kind


def test_the_stack_is_three_delta_layers_and_one_attention_layer():
    core, _, params, _, _, state = make(tiny_cc())
    assert [m.layer_name for m in core.kc.mixers] == ["gdn"] * 3 + ["gattn"]
    assert core.kc.layers == 4 and core.kc.first_dense == 0
    assert (core.kc.gdn_key_heads, core.kc.gdn_value_heads) == (2, 4)
    assert sorted(params["layer_1"]) == ["ffn_norm", "gdn", "mix_norm", "moe"]
    assert sorted(params["layer_4"]) == ["ffn_norm", "gattn", "mix_norm", "moe"]
    assert sorted(state["layer_3"]) == ["S", "conv"]
    assert state["layer_3"]["conv"].shape == (3, 3, 2 * 16 + 32)
    assert sorted(state["layer_4"]) == ["head", "k", "v", "valid"]
    assert sorted(params["layer_2"]["moe"]) == [
        "experts", "router", "shared", "shared_gate"]
    assert core.stat_names == (
        "moe_expert_load_max_over_mean", "moe_held_assign_share",
        "moe_tokens_dropped", "kda_fused_tile_share", "gattn_live_key_share",
        "kda_scalar_gate_share")


@pytest.mark.parametrize("gate", ["one_wide", "broadcast"])
@pytest.mark.parametrize("steps,chunk,block", [
    (20, 8, 4), (80, 40, 8), (27, 8, 4)])
def test_chunked_scan_with_a_scalar_gate_matches_the_published_step(
        steps, chunk, block, gate):
    """`kda_chunked` handed one log-decay a head, one channel wide (the
    scalar form of the in-chunk preparation, what `_GatedDeltaNet` hands it)
    or broadcast over the key channels (`_prep_plain`, the definition),
    against a loop of the published Gated DeltaNet step
    (S <- exp(g) S; S <- S + beta k (v - S^T k)^T; o = S^T q), with resets
    inside a chunk and from a non-zero state: values, final state and the
    gradients of every input.  27 steps are padded to 32."""
    b, h, dk, dv = 2, 4, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(steps), 6)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, steps, h, dk)))
    k = unit(jax.random.normal(ks[1], (b, steps, h, dk)))
    v = jax.random.normal(ks[2], (b, steps, h, dv))
    g = -jax.random.uniform(ks[3], (b, steps, h), minval=0.01, maxval=3.0)
    beta = jax.random.uniform(ks[4], (b, steps, h))
    s0 = 0.3 * jax.random.normal(ks[5], (b, h, dk, dv))
    resets = jnp.zeros((b, steps), bool).at[0, 3].set(True).at[1, 5].set(True)
    seg = jnp.cumsum(resets.astype(jnp.int32), axis=1)

    width = 1 if gate == "one_wide" else dk
    assert kl.kda_prep_path(width, dk, dv, chunk, block) == (
        "scalar" if gate == "one_wide" else "plain")

    def chunked(q, k, v, g, beta):
        wide = jnp.broadcast_to(g[..., None], (b, steps, h, width))
        return kl.kda_chunked(q, k, v, wide, beta, seg, s0, chunk, block,
                              jnp.float32)

    def published(q, k, v, g, beta):
        def one(s, xs):
            q_t, k_t, v_t, g_t, b_t, r_t = xs
            s = jnp.where(r_t[:, None, None, None], 0.0, s)
            s = s * jnp.exp(g_t)[..., None, None]
            mem = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision="highest")
            delta = (v_t - mem) * b_t[..., None]
            s = s + k_t[..., :, None] * delta[..., None, :]
            return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision="highest")

        mv = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
        s, o = jax.lax.scan(
            one, s0, (mv(q), mv(k), mv(v), mv(g), mv(beta), mv(resets)))
        return jnp.moveaxis(o, 0, 1), s

    wo = jax.random.normal(jax.random.PRNGKey(9), (b, steps, h, dv))

    def both(f):
        def loss(*a):
            o, s = f(*a)
            return jnp.sum(o * wo) + jnp.sum(s), (o, s)
        return jax.jit(jax.value_and_grad(loss, range(5), has_aux=True))

    (_, got), grads = both(chunked)(q, k, v, g, beta)
    (_, want), grads_want = both(published)(q, k, v, g, beta)
    for a, c in zip(got, want):
        close(a, c)
    for a, c in zip(grads, grads_want):
        close(a, c, 2e-3)


@pytest.mark.parametrize("shift", [0, 7, 100000])
def test_rotation_of_halves_against_the_published_form(shift):
    """The program's `rotate_halves` over the rotary dimensions against the
    reference's `u cos + rotate_half(u) sin`; and a score depends on the
    difference of the two positions alone (a common shift changes nothing,
    which is what lets the window carry un-rotated keys)."""
    theta, rot, d = 1e7, 64, 256
    kq, kk = jax.random.split(jax.random.PRNGKey(shift))
    q = jax.random.normal(kq, (2, 9, 3, d))
    k = jax.random.normal(kk, (2, 9, 3, d))
    pos = jnp.arange(9)
    # float32 angles at position 1e5 are good to about 0.01 rad: the reason
    # the program keeps every position under W + T
    far = shift >= 1000
    prog = lambda u, s: jnp.concatenate(  # noqa: E731
        [qn.rotate_halves(u[..., :rot], pos + s, theta), u[..., rot:]], axis=-1)
    close(prog(q, shift), ref.rope_halves(q, pos + shift, rot, theta),
          1e-2 if far else 1e-6)
    np.testing.assert_array_equal(prog(q, shift)[..., rot:], q[..., rot:])
    score = lambda a, b: jnp.einsum(  # noqa: E731
        "bthd,bshd->bhts", a, b, precision=jax.lax.Precision.HIGHEST)
    close(score(prog(q, shift), prog(k, shift)),
          score(ref.rope_halves(q, pos, rot, theta),
                ref.rope_halves(k, pos, rot, theta)), 3e-2 if far else 1e-5)


def test_the_sixteen_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """512 experts under a softmax router, 10 a token, one gated shared
    expert: 16 shares of 32 (experts 0-31, 32-63, ...), each computed by the
    program's layer told which 32 it holds; the gated shared expert, which
    every chip computes alike, counted once: their sum is the uncut reference
    layer."""
    cc = tiny_cc(num_experts=512, num_experts_per_tok=10)
    x = jax.random.normal(jax.random.PRNGKey(0), (60, cc["hidden_size"]))
    cfg = qn.Qwen3NextConfig.from_dict({**cc, "experts_here": 512})
    assert (cfg.experts, cfg.top_k, cfg.route, cfg.shared_gate,
            cfg.route_scale) == (512, 10, "softmax", True, 1.0)
    p, _ = cf.expert_layer(cfg, x)
    p["router"]["select_bias"] = 0.002 * jax.random.normal(
        jax.random.PRNGKey(2), (512,))
    whole = ref.moe_ffn(p, cc, x, (0, 512), ref.plain_dot)
    shared = ref.moe_ffn(p, cc, x, (0, 0), ref.plain_dot)  # no expert held
    close(shared, jax.nn.sigmoid(x @ p["shared_gate"]["kernel"])
          * ref.swiglu(p["shared"], x, ref.plain_dot))
    cf.shares_add_up(cfg, cc, ref, p, x, 32, whole, shared)


def test_no_token_is_dropped_when_every_token_picks_the_held_experts():
    """The worst case of the row buffer at this geometry: 32 experts in all,
    all held, 10 a token, and enough tokens that the buffer is picked among
    three sizes (the largest, n x 10, is the one taken)."""
    cc = tiny_cc(num_experts=32, num_experts_per_tok=10)
    cfg = qn.Qwen3NextConfig.from_dict({**cc, "experts_here": 32})
    x = jax.random.normal(jax.random.PRNGKey(0), (300, cc["hidden_size"]))
    p, run = cf.expert_layer(cfg, x)
    y, stats = run(p, x)
    assert float(stats["moe_tokens_dropped"]) == 0.0
    assert float(stats["moe_held_assign_share"]) == 1.0
    close(y, ref.moe_ffn(p, cc, x, (0, 32), ref.plain_dot))


@pytest.mark.parametrize("steps,filled,lane,share", [
    (40, 0, False, 820 / (40 * 40)),  # the burn-in from a sequence's start
    (80, 40, False, (80 * 40 + 3240) / (80 * 120)),  # the trained slice after
    (1, 120, True, 1.0),  # a warmed actor's tick: written first, its ring whole
])
def test_live_key_share_of_the_learn_steps_two_passes(
        steps, filled, lane, share):
    """`gattn_live_key_share`: the share of score columns the mask leaves, at
    the published window and sequence lengths (tiny widths)."""
    cc = tiny_cc(window=120, **SHORT)
    core, stack, params, _, _, state = make(cc, batch=1, steps=2, reset_at=())
    x = jax.random.normal(jax.random.PRNGKey(1), (1, filled + steps, FEATURES))
    none = jnp.zeros((1, filled + steps), bool)
    if not lane:  # the learner's passes: from a sequence's zero-slot start
        state = core.from_stored(jnp.zeros((1, 0)), jnp.zeros((1, 0)))
    if filled:
        _, state = jitted(cc)[0](
            params, x[:, :filled], state, none[:, :filled])
    _, sown = cf.jitted_sown(FAMILY, cc)(
        params, x[:, filled:], state, none[:, filled:])
    stats = reduce_stats(sown)
    assert float(stats["gattn_live_key_share"]) == pytest.approx(share, rel=1e-6)
    # a sequence's preparation ran the scalar form and no tile kernel; a
    # tick prepares nothing and sows neither
    if steps > 1:
        assert float(stats["kda_scalar_gate_share"]) == 1.0
        assert float(stats["kda_fused_tile_share"]) == 0.0
    else:
        assert not {"kda_scalar_gate_share", "kda_fused_tile_share"} & set(stats)


def test_the_published_file_reads_the_published_sizes():
    with open(cf.FAMILIES[FAMILY].published_path) as f:
        cc = json.load(f)
    kc = qn.Qwen3NextConfig.from_dict(cc)
    assert [m.layer_name for m in kc.mixers] == ["gdn", "gdn", "gdn", "gattn"]
    assert (kc.hidden, kc.gdn_key_heads, kc.gdn_value_heads, kc.gdn_key_dim,
            kc.gdn_value_dim, kc.conv_kernel, kc.chunk, kc.block) == (
        2048, 16, 32, 128, 128, 4, 40, 8)
    assert (kc.attn_heads, kc.attn_kv_heads, kc.attn_head_dim,
            kc.attn_rotary_dim, kc.window, kc.rope_theta) == (
        16, 2, 256, 64, 120, 1e7)
    assert (kc.experts, kc.experts_here, kc.first_expert, kc.top_k,
            kc.expert_width, kc.shared_width, kc.eps) == (
        512, 32, 0, 10, 512, 512, 1e-6)
    # 3 x (S 32x128x128 + a tail of 3 x 8,192) + 2 x 120x2x256 + 120 and
    # the ring's head, float32
    assert state_bytes_per_lane(qn.Qwen3NextCore(kc)) == (
        3 * 2_195_456 + 492_004) == 7_078_372
    for key, bad in (("mlp_only_layers", [2]), ("decoder_sparse_step", 2),
                     ("use_sliding_window", True), ("norm_topk_prob", False),
                     ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match="not written"):
            qn.Qwen3NextConfig.from_dict({**cc, key: bad})


@pytest.mark.parametrize("family,published,pinned", [
    ("kimi_linear", "kimi_linear_48b_a3b.json", "kimi_core_pinned.json"),
    ("deepseek_v3", "kanana_2_30b_a3b.json", "kanana_core_pinned.json"),
])
def test_the_other_cores_trees_are_leaf_for_leaf_what_they_were(
        family, published, pinned):
    """A layer of `CoreConfig` names its mixer and a mixer gives its own zero
    state: the Kimi-Linear and DeepSeek-V3 cores' parameter trees (which
    benchmarks/weights_core.py walks by name) and per-lane states at the
    published sizes are, path for path and shape for shape, what the tree
    before that built (recorded from it)."""
    with open(os.path.join(cf.HERE, "fixtures", pinned)) as f:
        want = json.load(f)
    fam = cf.FAMILIES[family]
    assert fam.published == published
    with open(fam.published_path) as f:
        core = fam.core(json.load(f), jnp.bfloat16)
    shapes, state, _ = cf.stack_shapes(
        core.kc, cf.TRUNK_FEATURES, jnp.bfloat16)
    assert cf.shapes_by_path(shapes) == want["published_param_shapes"]
    assert cf.shapes_by_path(state) == want["published_state_shapes"]
    assert (core.kc.route, core.kc.shared_gate) == ("sigmoid", False)
