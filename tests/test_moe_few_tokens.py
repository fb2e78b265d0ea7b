"""`_MoE`'s few-token branch (models/mla_moe.py: a walk over the held experts
that skips every one no token chose, where the many-token path sorts rows
into one grouped product): against the plain references' `moe_ffn` at act
shapes, what it traces to at the published LFM2 sizes, and the counter it
sows, `moe_act_touched_expert_share`, up to the fused trainer's rows."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import cores, lfm2, mla_moe, qwen3_next
from rainbow_iqn_apex_tpu.models.cores import CORE_STATS, reduce_stats

import core_families as cf
import reference_lfm2_core as ref_lfm2
import reference_qwen3_next_core as ref_qwen3
from core_families import close, equations, grads_close, run_fused_cli

LANES = 16  # tokens of a tick in every fused cell
HELD = 4

# family -> (configuration, its reader, the reference): a sigmoid router with
# no shared expert, a softmax router beside a gated shared expert; 4 of the
# experts held, so a routing can touch none, one, two or all of them
FAMILIES = {
    "lfm2": (cf.tiny_cc("lfm2_moe", num_experts=8, experts_here=HELD),
             lfm2.Lfm2Config, ref_lfm2),
    "qwen3_next": (cf.tiny_cc("qwen3_next", num_experts=16, experts_here=HELD),
                   qwen3_next.Qwen3NextConfig, ref_qwen3),
}


def routing_bias(routing, experts, top_k):
    """A selection bias that decides the choice whatever the scores (which
    lie in (0, 1)), and the touched share it makes; None: what the scores
    happen to give.  Experts 0..HELD-1 are held, the others absent."""
    bias = np.zeros(experts, np.float32)
    if routing == "none_held":  # every choice falls on an absent expert
        bias[HELD:HELD + top_k] = 10.0
        return bias, 0.0
    if routing == "one_held_for_every_lane":  # the cells' seeded case
        bias[1] = 10.0
        bias[HELD:HELD + top_k - 1] = 10.0
        return bias, 1.0 / HELD
    if routing == "every_held_touched":  # a router that spreads
        bias[HELD:] = -10.0
        return bias, 1.0
    assert routing == "two_held_for_a_token"
    # expert 0 for every token, and of held 2 and absent HELD + 1 and up
    # whichever scores higher: some tokens weigh two held experts, some one
    bias[0] = 10.0
    bias[[2] + list(range(HELD + 1, HELD + top_k))] = 5.0
    return bias, None


def built(family, dtype, routing, n=LANES):
    cc, reader, ref = FAMILIES[family]
    kc = reader.from_dict(cc)
    moe = mla_moe._MoE(kc, dtype)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, kc.hidden))
    p = jax.jit(moe.init)(jax.random.PRNGKey(4), x)["params"]
    bias, share = routing_bias(routing, kc.experts, kc.top_k)
    p["router"]["select_bias"] = jnp.asarray(bias)
    return cc, kc, moe, ref, p, x, share


def reference(ref, cc, dtype):
    """`ref.moe_ffn` with the program's precisions: products on `dtype`
    operands summed in float32, the router's in float32 throughout."""
    def run(p, x):
        def dot(a, w):
            if dtype == jnp.float32 or w is p["router"]["kernel"]:
                return ref.plain_dot(a, w)
            return jnp.dot(a.astype(dtype), w.astype(dtype),
                           preferred_element_type=jnp.float32)
        return ref.moe_ffn(p, cc, x, (0, HELD), dot)
    return run


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", [
    "none_held", "one_held_for_every_lane", "every_held_touched",
    "two_held_for_a_token"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_few_token_branch_matches_the_reference_values_and_gradients(
        family, routing, dtype):
    """Values to the core tests' 2e-4 and gradients to their 2e-3 in float32
    (sums in another order alone); in bfloat16 both sides round the same
    operands, and a float32 sum in another order can turn the rounding of a
    hidden unit by one step of 2^-8: 1e-2 and 3e-2."""
    dtype = jnp.dtype(dtype)
    cc, kc, moe, ref, p, x, share = built(family, dtype, routing)
    assert LANES * min(kc.top_k, HELD) <= mla_moe.FEW_ROWS
    tol, gtol = (2e-4, 2e-3) if dtype == jnp.float32 else (1e-2, 3e-2)
    plain = reference(ref, cc, dtype)
    y, sown = jax.jit(lambda p, x: moe.apply(
        {"params": p}, x, mutable=[CORE_STATS]))(p, x)
    want = plain(p, x)
    close(y, want, tol)

    stats = reduce_stats(sown)
    logits = ref.plain_dot(x, p["router"]["kernel"])
    scores = (jax.nn.softmax(logits, axis=-1) if kc.route == "softmax"
              else jax.nn.sigmoid(logits))
    local = np.asarray(jax.lax.top_k(
        scores + p["router"]["select_bias"], kc.top_k)[1])
    held_per_token = (local < HELD).sum(axis=-1)
    touched = len(set(local[local < HELD].tolist()))
    assert float(stats["moe_act_touched_expert_share"]) == touched / HELD
    if share is not None:
        assert touched / HELD == share
    else:  # its two weights must both count: the comparison above holds them
        assert held_per_token.max() == 2 and held_per_token.min() == 1
    if routing == "none_held":  # the shared expert's part alone, or nothing
        if kc.shared_width:
            assert float(jnp.abs(y).max()) > 0
        else:
            assert not np.any(np.asarray(y))
    assert float(stats["moe_held_assign_share"]) == pytest.approx(
        held_per_token.sum() / (LANES * kc.top_k))
    assert float(stats["moe_tokens_dropped"]) == 0.0
    # no buffer is taken: over the rows that would hold every assignment
    assert float(stats["moe_row_fill_share"]) == pytest.approx(
        held_per_token.sum() / (LANES * min(kc.top_k, HELD)))

    wgt = jax.random.normal(jax.random.PRNGKey(5), y.shape)
    g = jax.jit(jax.grad(lambda p, x: jnp.sum(
        moe.apply({"params": p}, x) * wgt), argnums=(0, 1)))(p, x)
    g_ref = jax.jit(jax.grad(lambda p, x: jnp.sum(plain(p, x) * wgt),
                             argnums=(0, 1)))(p, x)
    grads_close(g, g_ref, gtol)  # kernels, router, shared expert and input
    if touched < HELD:  # an expert no token chose takes no gradient
        idle = sorted(set(range(HELD)) - set(local[local < HELD].tolist()))
        for leaf in g[0]["experts"].values():
            assert not np.any(np.asarray(leaf)[idle])


def test_the_kernels_of_an_expert_no_token_chose_are_not_read():
    """One held expert for every lane: the other held experts' kernels are
    not read, so garbage in them changes no bit of the output."""
    _, _, moe, _, p, x, _ = built("lfm2", jnp.float32,
                                  "one_held_for_every_lane")
    run = jax.jit(lambda p, x: moe.apply({"params": p}, x))
    y = run(p, x)
    for name, leaf in p["experts"].items():
        p["experts"][name] = leaf.at[jnp.asarray([0, 2, 3])].set(jnp.nan)
    assert np.array_equal(np.asarray(run(p, x)), np.asarray(y))


def _published_lfm2_jaxpr(n):
    core = cores._load("configs/cores/lfm2_8b_a1b.json", "bfloat16")
    kc = core.kc
    moe = mla_moe._MoE(kc, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((n, kc.hidden), jnp.float32)
    params = jax.eval_shape(lambda x: moe.init(jax.random.PRNGKey(0), x), x)
    traced = jax.make_jaxpr(lambda p, x: moe.apply(
        p, x, mutable=[CORE_STATS]))(params, x)
    return kc, list(equations(traced.jaxpr))


def test_a_tick_at_the_published_lfm2_sizes_casts_no_stack_of_kernels():
    """16 tokens, bfloat16, 8 held experts of 2048 x 1792 (shapes alone, no
    array is made): no equation's output is a bfloat16 array of the stacks'
    shape, which the grouped product's `w.astype` made of each every tick;
    the casts that remain are of one expert's kernels."""
    kc, eqs = _published_lfm2_jaxpr(LANES)
    e, f, w = kc.experts_here, kc.hidden, kc.expert_width
    assert (e, f, w) == (8, 2048, 1792)
    outs = [v.aval for eq in eqs for v in eq.outvars]
    assert not [a for a in outs if a.dtype == jnp.bfloat16
                and a.shape in ((e, f, w), (e, w, f))]
    cast = [a.shape for a in outs if a.dtype == jnp.bfloat16
            and a.shape in ((f, w), (w, f))]
    assert sorted(cast) == sorted([(f, w), (f, w), (w, f)] * e)
    names = [eq.primitive.name for eq in eqs]
    assert names.count("cond") == e and "ragged_dot_general" not in names
    assert "sort" not in names and "while" not in names


def test_the_many_token_path_traces_the_primitives_it_did():
    """7,680 tokens (a learn step's batch) at the published LFM2 sizes: the
    sort, the `switch` over the row buffers, the grouped products and the
    counters trace to the recorded sequence of primitives: PR 43's, which the
    few-token branch left as they were (PR 44), but for the ladder (PR 49:
    an empty rung and two rungs with rows, three grouped products each, the
    top one under a `checkpoint`)."""
    _, eqs = _published_lfm2_jaxpr(7680)
    with open(os.path.join(
            cf.HERE, "fixtures", "moe_many_token_primitives.json")) as f:
        recorded = json.load(f)
    assert [eq.primitive.name for eq in eqs] == recorded["primitives"]
    assert recorded["primitives"].count("ragged_dot_general") == 6
    assert recorded["primitives"].count("remat2") == 1


@pytest.mark.parametrize("routing,share", [
    ("none_held", 0.0), ("one_held_for_every_lane", 1.0 / HELD),
    ("every_held_touched", 1.0)])
def test_touched_expert_share_is_sown_on_the_few_token_path_only(
        routing, share):
    _, kc, moe, _, p, x, _ = built("lfm2", jnp.float32, routing)
    sown = jax.jit(lambda p, x: reduce_stats(moe.apply(
        {"params": p}, x, mutable=[CORE_STATS])[1]))
    assert float(sown(p, x)["moe_act_touched_expert_share"]) == share
    many = mla_moe.FEW_ROWS // min(kc.top_k, HELD) + 1
    stats = sown(p, jnp.tile(x, (many // LANES + 1, 1)))
    assert "moe_act_touched_expert_share" not in stats
    assert "moe_row_fill_share" in stats


def test_the_fused_trainers_rows_carry_the_ticks_touched_expert_share(
        tmp_path):
    """The tick's counter is a trailing output of the segment, after the
    learn steps' counters, and a row's value the mean over the dispatch's
    ticks: of the tiny LFM2 core's 2 held experts a tick touches none, one
    or both in each of four layers, so a multiple of 1/8 a tick."""
    from rainbow_iqn_apex_tpu.models.cores import LSTMCore

    learn = run_fused_cli(tmp_path, "lfm2")
    assert len(learn) >= 2
    for row in learn:
        assert 0.0 <= row["moe_act_touched_expert_share"] <= 1.0
        assert row["moe_act_touched_expert_share"] * 8 * 8 == pytest.approx(
            round(row["moe_act_touched_expert_share"] * 8 * 8))
    core = cores._load("tests/fixtures/lfm2_core_tiny.json", "float32")
    assert core.act_stat_names == ("moe_act_touched_expert_share",
                                   "attn_act_window_written_share")
    assert "moe_act_touched_expert_share" not in core.stat_names
    ouro = cores._load("tests/fixtures/ouro_core_tiny.json", "float32")
    assert ouro.act_stat_names == ("attn_act_window_written_share",)
    assert LSTMCore().act_stat_names == ()


def test_the_ticks_written_window_share_is_one_slot_of_the_ring(tmp_path):
    """`attn_act_window_written_share` beside it: a tick writes one of its
    ring's 12 slots in every attention layer of the tiny Ouro core, so 1 / 12
    in every row (1.0 would say a window was rewritten whole); a core without
    attention windows, the LSTM or a stack of delta-rule mixers alone, names
    no such counter and its ticks sow none."""
    from rainbow_iqn_apex_tpu.models.cores import LSTMCore

    learn = run_fused_cli(tmp_path, "ouro")
    assert len(learn) >= 2
    assert all(row["attn_act_window_written_share"] == pytest.approx(1 / 12)
               for row in learn)
    assert "attn_act_window_written_share" not in LSTMCore().act_stat_names
    qwen3 = cores._load("tests/fixtures/qwen3_next_core_tiny.json", "float32")
    kc = qwen3.kc
    delta = dataclasses.replace(qwen3, kc=dataclasses.replace(
        kc, mixers=tuple(m for m in kc.mixers if m.layer_name == "gdn")))
    assert delta.act_stat_names == ("moe_act_touched_expert_share",)
    x = jnp.zeros((2, 1, kc.hidden))
    stack = mla_moe._Stack(delta.kc, jnp.float32)
    state, none = delta.initial_state(2), jnp.zeros((2, 1), bool)
    params = jax.jit(stack.init)(
        jax.random.PRNGKey(0), x, state, none)["params"]
    sown = jax.jit(lambda p: stack.apply(
        {"params": p}, x, state, none, mutable=[CORE_STATS])[1])(params)
    assert "attn_act_window_written_share" not in reduce_stats(sown)
    # and its reset is the multiply of every leaf
    warm, keep = jax.tree.map(jnp.ones_like, state), jnp.asarray([1, 0])
    for a, b in zip(jax.tree.leaves(delta.reset_lanes(warm, keep)),
                    jax.tree.leaves(cores.zero_lanes(warm, keep))):
        np.testing.assert_array_equal(a, b)
