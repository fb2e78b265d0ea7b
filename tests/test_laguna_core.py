"""The Laguna core (models/laguna.py over models/mla_moe.py) against its plain
float32 reference (tests/reference_laguna_core.py), at tiny widths (a full
layer of 6 query heads and two sliding layers of 8 over 2 key/value heads of
16, sliding span 8 under a memory of 16 or more, a YaRN rotation on half of
the full layer's heads, 8 experts of which 4 are held and 2 a token, a shared
expert), float32 compute, seeded weights: what is this family's own (the
cases every family shares are tests/test_core_reference.py's and the two
window files', which set both spans alike): two spans in one stack on
sequences longer than the shorter, attention by blocks against the dense
form, rings of two lengths in one lane, YaRN's table, the shares of the
expert layer, and what the reader refuses."""

import functools
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import laguna, mla_moe
from rainbow_iqn_apex_tpu.models.cores import reduce_stats
from rainbow_iqn_apex_tpu.obs import device_scopes as ds

import core_families as cf
import reference_laguna_core as ref
from core_families import close, grads_close
from ring_windows import live, window_slots

FAMILY = "laguna"
SLIDING = 8  # the tiny file's sliding span
tiny_cc = functools.partial(cf.tiny_cc, FAMILY, sliding_window=SLIDING)
make = functools.partial(cf.make, FAMILY)
jitted = functools.partial(cf.jitted, FAMILY)


def test_the_stack_is_a_full_layer_and_two_sliding_ones_each_with_its_own():
    core, _, params, _, _, state = make(tiny_cc(window=16))
    kc = core.kc
    assert [(m.layer_name, m.geom.kind, m.geom.heads, m.span(kc))
            for m in kc.mixers] == [("gqa", "full", 6, 16),
                                    ("gqa", "sliding", 8, 8),
                                    ("gqa", "sliding", 8, 8)]
    assert (kc.layers, kc.passes, kc.out_norms, kc.first_dense) == (
        3, 1, False, 1)
    assert (kc.experts, kc.top_k, kc.experts_here, kc.first_expert,
            kc.shared_width, kc.route, kc.route_scale, kc.shared_gate) == (
                8, 2, 4, 0, 16, "sigmoid", 2.5, False)
    assert sorted(params) == ["final_norm", "in_proj", "layer_1", "layer_2",
                              "layer_3"]
    assert sorted(params["layer_1"]) == ["ffn", "ffn_norm", "gqa", "mix_norm"]
    assert sorted(params["layer_2"]) == ["ffn_norm", "gqa", "mix_norm", "moe"]
    # a layer's projections go by its own heads; no q/k norm, a gate a head
    assert jax.tree.map(jnp.shape, params["layer_1"]["gqa"]) == {
        "q_proj": {"kernel": (32, 96)}, "k_proj": {"kernel": (32, 32)},
        "v_proj": {"kernel": (32, 32)}, "g_proj": {"kernel": (32, 6)},
        "o_proj": {"kernel": (96, 32)}}
    assert jax.tree.map(jnp.shape, params["layer_2"]["gqa"]) == {
        "q_proj": {"kernel": (32, 128)}, "k_proj": {"kernel": (32, 32)},
        "v_proj": {"kernel": (32, 32)}, "g_proj": {"kernel": (32, 8)},
        "o_proj": {"kernel": (128, 32)}}
    assert sorted(params["layer_2"]["moe"]) == ["experts", "router", "shared"]
    # the state: rings of two lengths side by side, every leaf led by lanes
    assert {k: v["k"].shape for k, v in state.items()} == {
        "layer_1": (3, 16, 2, 16), "layer_2": (3, 8, 2, 16),
        "layer_3": (3, 8, 2, 16)}
    assert window_slots(cf.sequence_start(core, 3)) == {0}
    assert core.stat_names == (
        "moe_expert_load_max_over_mean", "moe_held_assign_share",
        "moe_tokens_dropped", "attn_live_key_share_full",
        "attn_live_key_share_sliding", "attn_band_key_share",
        "moe_row_fill_share")
    assert core.act_stat_names == (
        "moe_act_touched_expert_share", "attn_act_window_written_share")


def test_a_sequence_of_two_and_a_half_sliding_spans_matches_the_reference():
    """T = 20 = 2.5 x the sliding span under a memory that holds it all: the
    full layer attends over the whole sequence and the sliding layers' band
    cuts most queries' keys, with cuts; values, gradients, and another
    span's pass differs."""
    cc = tiny_cc(window=32)
    core, stack, params, x, resets, state = make(cc)
    w = jax.random.normal(jax.random.PRNGKey(4), (*x.shape[:2], 32))
    run, plain = jitted(cc)
    start = cf.sequence_start(core, x.shape[0])
    prog = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(run(p, x, start, resets)[0] * w)))
    want = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(plain(p, x, resets) * w)))
    y, st = run(params, x, start, resets)
    close(y, plain(params, x, resets))
    grads_close(prog(params)[1], want(params)[1])
    assert {k: v["k"].shape[1] for k, v in st.items()} == {
        "layer_1": 20, "layer_2": 8, "layer_3": 8}
    wider = jax.jit(lambda p: ref.core_forward(
        p, {**cc, "sliding_window": 12}, x, resets))(params)
    assert float(jnp.abs(y - wider).max()) > 1e-3
    assert float(jnp.abs(y - jax.jit(lambda p: ref.core_forward(
        p, cc, x, resets, ignore_span=True))(params)).max()) > 1e-3


@pytest.mark.parametrize("burn", [SLIDING, SLIDING + 3])
def test_a_burn_in_as_long_as_the_sliding_span_then_a_slice(burn):
    """The learn step's two passes with a burn-in of exactly the sliding span
    (and of three steps more): the sliding layers' windows have grown to
    `span` slots, a ring by shape, and the slice's steps attend over it in
    age order; values and the gradient of the trained slice against the
    reference's one pass with its stop-gradient boundary, cuts on both
    sides."""
    cc = tiny_cc(window=32)
    steps = burn + 12
    core, stack, params, x, resets, _ = make(
        cc, steps=steps, reset_at=((0, 2), (1, burn + 3)))
    w = jax.random.normal(jax.random.PRNGKey(5), (x.shape[0], 12, 32))
    run, plain = jitted(cc)
    start = cf.sequence_start(core, x.shape[0])

    def prog(p):
        _, st = run(p, x[:, :burn], start, resets[:, :burn])
        assert st["layer_2"]["k"].shape[1] == SLIDING
        st = jax.lax.stop_gradient(st)
        y = run(p, x[:, burn:], st, resets[:, burn:])[0]
        return jnp.sum(y * w), y

    def want(p):
        y = plain(p, x, resets, burn=burn)[:, burn:]
        return jnp.sum(y * w), y

    (_, y), grads = jax.jit(jax.value_and_grad(prog, has_aux=True))(params)
    (_, y_ref), grads_ref = jax.jit(jax.value_and_grad(want, has_aux=True))(
        params)
    close(y, y_ref)
    grads_close(grads, grads_ref)


def _qkv(t, n, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    b, g, r, d, s = 2, 2, 3, 8, n + t
    q = jax.random.normal(ks[0], (b, t, g, r, d))
    k = jax.random.normal(ks[1], (b, s, g, d))
    v = jax.random.normal(ks[2], (b, s, g, d))
    valid = (jax.random.uniform(ks[3], (b, s)) > 0.2).astype(
        jnp.float32).at[:, n:].set(1.0)
    seg_q = jnp.cumsum(jax.random.uniform(ks[4], (b, t)) > 0.85, axis=1)
    seg_k = jnp.concatenate([jnp.zeros((b, n), seg_q.dtype), seg_q], axis=1)
    return q, k, v, valid, seg_k, seg_q


@pytest.mark.parametrize("span", [6, 11, 40])
@pytest.mark.parametrize("t,n,block", [(23, 0, 5), (17, 9, 4), (12, 7, 12)])
def test_attention_by_blocks_is_the_dense_form(t, n, block, span):
    """`attend_by_blocks` at a block that does not divide T (and at one
    block) against `attend` over every slot under the mask written out:
    values to 1e-6 and gradients to 1e-5 of the largest (a key outside a
    block's band weighs exactly 0 in the dense form; the sums run over fewer
    zeros), the live entries counted alike, the columns computed the band's."""
    q, k, v, valid, seg_k, seg_q = _qkv(t, n)
    pos_q, pos_k = (n + jnp.arange(t))[:, None], jnp.arange(n + t)[None]
    mask = ((pos_k <= pos_q) & (pos_k > pos_q - span))[None] & (
        valid[:, None] > 0) & (seg_k[:, None] == seg_q[:, :, None])
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def blocks(q, k, v):
        o, alive, computed = mla_moe.attend_by_blocks(
            q, k, v, valid, seg_k, seg_q, span, jnp.float32, block)
        return jnp.sum(o * w), (o, alive, computed)

    def dense(q, k, v):
        o = mla_moe.attend(q, k, v, mask, jnp.float32)
        return jnp.sum(o * w), o

    (_, (o, alive, computed)), g = jax.value_and_grad(
        blocks, (0, 1, 2), has_aux=True)(q, k, v)
    (_, o_dense), g_dense = jax.jit(jax.value_and_grad(
        dense, (0, 1, 2), has_aux=True))(q, k, v)
    close(o, o_dense, 1e-6)
    for a, b in zip(g, g_dense):
        close(a, b, 1e-5)
    assert float(alive) == float(jnp.sum(mask))
    want = sum((min(i + block, t) - i)
               * (n + min(i + block, t) - max(n + i - span + 1, 0))
               for i in range(0, t, block))
    assert computed == want <= t * (n + t)
    if span >= n + t and block >= t:
        assert computed == t * (n + t)  # nothing to leave out: the dense form


def test_the_stack_by_small_blocks_is_the_stack_by_one(monkeypatch):
    """The whole stack with 5 queries a block (T = 23: blocks of 5, 5, 5, 5
    and 3) against the same at one block a call, values and gradients; the
    sliding layers then compute a band of the slots and say so."""
    cc = tiny_cc(window=32)
    core, stack, params, x, resets, _ = make(cc, steps=23)
    start = cf.sequence_start(core, x.shape[0])
    w = jax.random.normal(jax.random.PRNGKey(4), (*x.shape[:2], 32))

    def run():  # a fresh trace each time: the block is read when traced
        def loss(p):
            (y, _), sown = stack.apply(
                {"params": p}, x, start, resets, mutable=["core_stats"])
            return jnp.sum(y * w), (y, reduce_stats(sown))
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    (_, (y1, s1)), g1 = run()
    monkeypatch.setattr(mla_moe, "ATTN_BLOCK", 5)
    (_, (y5, s5)), g5 = run()
    close(y5, y1, 1e-5)
    grads_close(g5, g1, 1e-4)
    assert float(s1["attn_band_key_share"]) == 1.0
    band = sum((min(i + 5, 23) - i) * (min(i + 5, 23) - max(i - 7, 0))
               for i in range(0, 23, 5)) / (23 * 23)
    assert float(s5["attn_band_key_share"]) == pytest.approx(band)
    for name in ("attn_live_key_share_full", "attn_live_key_share_sliding"):
        assert float(s5[name]) == pytest.approx(float(s1[name]))


def test_ticks_on_rings_of_two_lengths_match_the_sequence_pass():
    """40 ticks from `initial_state`: the sliding layers' rings of 8 go round
    five times and the full layer's ring of 16 two and a half, lane 0 cut
    before tick 7 and lane 1 before ticks 19 and 20 through the core's reset;
    against the program's pass over the sequence, the reference's absolute
    positions, and a tick writes one slot of each ring."""
    cc = tiny_cc(window=16)
    steps, cuts = 40, ((0, 7), (1, 19), (1, 20))
    core, stack, params, x, resets, state = make(
        cc, batch=2, steps=steps, reset_at=cuts)
    assert window_slots(state) == {8, 16}
    run, plain = jitted(cc)
    none = jnp.zeros_like(resets)
    reset = jax.jit(core.reset_lanes)

    def cut(st, i):
        return reset(st, jnp.asarray(
            [(b, i) not in cuts for b in range(2)], jnp.uint8))

    ticks, st = cf.ticks_from(run, params, x, none, state, cut)
    seq, seq_state = run(params, x, state, resets)
    close(ticks, seq)
    close(ticks, plain(params, x, resets, window=16))
    cf.states_close(st, seq_state, live)
    for name, slots in (("layer_1", 16), ("layer_2", 8), ("layer_3", 8)):
        np.testing.assert_array_equal(
            st[name]["head"], [(steps - 7) % slots, (steps - 20) % slots])
    # the memory matters: a longer one's pass differs
    assert float(jnp.abs(ticks - plain(
        params, x, resets, window=32)).max()) > 1e-3
    _, sown = cf.jitted_sown(FAMILY, cc)(
        params, x[:, :1], state, resets[:, :1])
    stats = reduce_stats(sown)
    assert float(stats["attn_act_window_written_share"]) == pytest.approx(
        (1 / 16 + 2 / 8) / 3)
    assert "attn_band_key_share" not in stats  # a tick runs no blocks


def test_the_live_and_band_shares_of_a_slice_after_a_burn_in_of_a_span():
    """The cell's shape at tiny widths: burn-in = slice = the sliding span,
    the memory twice that.  Every trained step of a sliding layer sees a
    full span of keys, half of the slots held; a full layer its causal part
    of all of them."""
    w = SLIDING
    cc = tiny_cc(window=2 * w)
    core, stack, params, _, _, _ = make(cc, batch=1, steps=2, reset_at=())
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2 * w, 24))
    none = jnp.zeros((1, 2 * w), bool)
    start = cf.sequence_start(core, 1)
    _, state = jitted(cc)[0](params, x[:, :w], start, none[:, :w])
    _, sown = cf.jitted_sown(FAMILY, cc)(params, x[:, w:], state, none[:, w:])
    stats = reduce_stats(sown)
    assert float(stats["attn_live_key_share_sliding"]) == 0.5
    assert float(stats["attn_live_key_share_full"]) == pytest.approx(
        (w * w + w * (w + 1) / 2) / (w * 2 * w))
    assert set(core.stat_names) <= set(stats)


def test_yarns_table_is_the_closed_form_at_the_published_parameters():
    """The 32 frequencies of the published full-attention rotation: pairs
    under 5 keep theta^(-2i/64), pairs from 16 on have it divided by 64,
    those between are the linear blend; the attention factor is
    0.1 ln(64) + 1 as published; and `rotate_table` of a head is the
    reference's `rope` of it, the un-rotated half passed through."""
    with open(cf.FAMILIES[FAMILY].published_path) as f:
        cc = json.load(f)
    rp = cc["rope_parameters"]["full_attention"]
    rot = laguna._rotation(rp, cc["head_dim"])
    assert len(rot.freq) == 32 and rot.factor == rp["attention_factor"]
    assert rot.factor == pytest.approx(0.1 * math.log(64) + 1, rel=1e-12)
    i = np.arange(32)
    plain = 500000.0 ** (-2.0 * i / 64)
    low = 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(500000))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(500000))
    assert (math.floor(low), math.ceil(high)) == (5, 16)
    ramp = np.clip((i - 5) / (16 - 5), 0, 1)
    np.testing.assert_allclose(
        rot.freq, plain / 64 * ramp + plain * (1 - ramp), rtol=1e-12)
    np.testing.assert_allclose(rot.freq[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(rot.freq[16:], plain[16:] / 64, rtol=1e-12)
    assert ref.inv_frequencies(rp, 128)[0] == 64
    np.testing.assert_allclose(
        ref.inv_frequencies(rp, 128)[1], rot.freq, rtol=1e-12)
    plain_rot = laguna._rotation(cc["rope_parameters"]["sliding_attention"], 128)
    np.testing.assert_allclose(
        plain_rot.freq, 10000.0 ** (-2.0 * np.arange(64) / 128), rtol=1e-12)
    assert plain_rot.factor == 1.0
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 128))
    pos = jnp.arange(9) + 1000
    for kind, table in (("full_attention", rot), ("sliding_attention", plain_rot)):
        want = ref.rope(u, pos, cc["rope_parameters"][kind])
        close(mla_moe.rotate_table(u, pos, table), want, 1e-5)
    np.testing.assert_array_equal(
        mla_moe.rotate_table(u, pos, rot)[..., 64:], u[..., 64:])


def test_a_score_under_a_table_goes_by_the_difference_of_positions_alone():
    """Why keys stay un-rotated in their slots under a scaled rotation too:
    q . k after both are turned by the table is the same at positions (p, s)
    and (p + c, s + c), factor and all."""
    rot = mla_moe.Rotation((1.0, 0.31, 0.07), 1.3)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 8))
    at = lambda p, s: jnp.sum(  # noqa: E731
        mla_moe.rotate_table(q, jnp.asarray([p]), rot)
        * mla_moe.rotate_table(k, jnp.asarray([s]), rot))
    assert float(at(9, 4)) == pytest.approx(float(at(1009, 1004)), rel=1e-4)
    assert abs(float(at(9, 4)) - float(at(9, 5))) > 1e-3


def test_the_sixteen_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """16 experts, 2 a token, one shared expert: 16 shares of one expert, each
    computed by the program's layer told which it holds; the shared expert,
    whole on every chip, counted once, their sum is the uncut reference
    layer."""
    cc = tiny_cc(num_experts=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (60, cc["hidden_size"]))
    cfg = laguna.LagunaConfig.from_dict({**cc, "experts_here": 16})
    assert (cfg.experts, cfg.top_k, cfg.shared_width, cfg.route_scale) == (
        16, 2, 16, 2.5)
    p, _ = cf.expert_layer(cfg, x)
    p["router"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))
    whole = ref.moe_ffn(p, cc, x, (0, 16), ref.plain_dot)
    shared = ref.swiglu(p["shared"], x, ref.plain_dot)
    cf.shares_add_up(cfg, cc, ref, p, x, 1, whole, shared)


def test_the_scopes_of_a_layer_wear_its_kind():
    """`attn_full` and `attn_sliding` round the mixer, inside `core_layer`;
    inside them `mha_proj`, `mha_attn` and `mha_rope`; the expert layers and
    the dense one as every core's."""
    cc, core, width = cf.tiny_core(FAMILY)
    text = cf.lowered_text(core.kc, width)
    paths = {ds.scope_path(m)
             for m in set(re.findall(r'"(jit\([^"]*)"', text))}
    for kind in (ds.ATTN_FULL, ds.ATTN_SLIDING):
        for inner in ((ds.MHA_PROJ,), (ds.MHA_ATTN,),
                      (ds.MHA_ATTN, ds.MHA_ROPE)):
            assert (ds.CORE_LAYER, kind, *inner) in paths, (kind, inner)
    assert {ds.ATTN_FULL, ds.ATTN_SLIDING} <= set(ds.ALL_SCOPES)
    for scope in (ds.MOE_ROUTE, ds.MOE_EXPERTS, ds.MOE_SHARED, ds.DENSE_FFN,
                  ds.CORE_NORM, ds.CORE_EMBED):
        assert any(scope in p for p in paths), scope


def test_the_published_file_reads_the_published_sizes_and_refuses_the_rest():
    with open(cf.FAMILIES[FAMILY].published_path) as f:
        cc = json.load(f)
    assert len(cc["layer_types"]) == len(cc["mlp_layer_types"]) == len(
        cc["num_attention_heads_per_layer"]) == cc["num_hidden_layers"] == 40
    assert all((k == "full_attention") == (i % 4 == 0) == (h == 48)
               for i, (k, h) in enumerate(zip(
                   cc["layer_types"], cc["num_attention_heads_per_layer"])))
    kc = laguna.LagunaConfig.from_dict(cc)
    assert [m.geom.kind for m in kc.mixers] == [
        "full", "sliding", "sliding", "sliding", "full"]
    assert (kc.hidden, kc.first_dense, kc.dense_width, kc.eps, kc.in_proj,
            kc.window) == (2048, 1, 8192, 1e-6, True, 1024)
    # the next period, from its first layer: no dense layer among them
    later = laguna.LagunaConfig.from_dict({**cc, "first_layer_here": 5,
                                           "layers_here": 4})
    assert later.first_dense == 0 and [m.geom.heads for m in later.mixers] == [
        64, 64, 64, 48]
    # a memory shorter than the published sliding span bounds that too
    short = laguna.LagunaConfig.from_dict(
        {**cc, "assumed": {"attn_window": 120}})
    assert [m.span(short) for m in short.mixers] == [120] * 5
    yarn = cc["rope_parameters"]["full_attention"]
    for key, bad in (
            ("attention_bias", True), ("gating", False),
            ("gating", "per-channel"),
            ("moe_apply_router_weight_on_input", True),
            ("norm_topk_prob", False),
            ("layer_types", ["full_attention", "linear_attention"] * 20),
            ("mlp_layer_types", ["sparse", "dense"] * 20),
            ("layers_here", 50),
            ("rope_parameters", {**cc["rope_parameters"], "full_attention": {
                **yarn, "rope_type": "llama3"}}),
            ("rope_parameters", {**cc["rope_parameters"], "full_attention": {
                **yarn, "mscale": 0.7}})):
        with pytest.raises(ValueError, match="not written"):
            laguna.LagunaConfig.from_dict({**cc, key: bad})
