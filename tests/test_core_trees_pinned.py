"""The accepted stack cores' trees, pinned: every parameter leaf, every leaf
of a lane's state and of a sequence's start state (by path and shape) and the
counters a core names, of the five families' tiny and published
configurations, against tests/fixtures/stack_trees_pr46.json, which was
written from the tree of PR 46 (the parent of the PR that gave a layer's
attention geometry to its mixer) by `cf.stack_shapes` and `from_stored`:
nothing is allocated.  A PR that means to change an accepted core's tree
writes the fixture anew and says so."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import core_families as cf

with open(os.path.join(cf.HERE, "fixtures", "stack_trees_pr46.json")) as f:
    PINNED = json.load(f)


@pytest.mark.parametrize("case", sorted(PINNED))
def test_an_accepted_cores_trees_are_the_parents_leaf_for_leaf(case):
    name, which = case.split(".")
    fam = cf.FAMILIES[name]
    with open(fam.tiny if which == "tiny" else fam.published_path) as f:
        cc = json.load(f)
    core = fam.core(cc)
    width = cf.TRUNK_FEATURES if core.kc.in_proj else cc["hidden_size"]
    params, state, _ = cf.stack_shapes(core.kc, width)
    none = jax.ShapeDtypeStruct((2, 0), jnp.float32)
    want = PINNED[case]
    assert cf.shapes_by_path(params) == want["params"]
    assert cf.shapes_by_path(state) == want["lane_state"]
    assert cf.shapes_by_path(
        jax.eval_shape(core.from_stored, none, none)) == want["sequence_start"]
    assert list(core.stat_names) == want["stat_names"]
    assert list(core.act_stat_names) == want["act_stat_names"]
