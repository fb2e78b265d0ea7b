"""The comparison that decides `correct` for the fused training cells.

What is compared is what the timed path itself produced: the first learning
dispatch of the jitted segment that the window then drives, from seeded
weights, at the cell's own ring, batch and sequence sizes.  That dispatch
holds the trainer's first k learn steps (k is fixed by the learn cadence;
one in the R2D2 cells).  The program's side of the comparison is read from
the dispatch's outputs and from the state it left (with k = 1 also the first
gradient, from Adam's first moment); the reference's side is the plain
reference following the same steps from the same weights, keys and ring rows.

Step 1 is followed exactly: before it every slot carries a priority the
benchmark seeded (or 1.0, on the rows the lanes appended), so the draw is
known from the seed and the key.  From step 2 on the draw depends on
priorities the earlier steps wrote, which the program never hands back, so a
dispatch of more than one step is followed on the reference's own draws and
only the size of the parameters' change is compared.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import nets

AMBIGUOUS = 1e-5  # a draw this near a slot's edge (share of total) may round either way


def follow(params0, target0, steps, sample, loss_fn, hp, priority0,
           mode=None, touched=None):
    """Drive the plain reference through `steps` learn steps.

    steps: [(sample key, learn key, beta)]; sample(priority, key, beta,
    touched) -> (batch, idx) draws and assembles from the ring's host copy;
    `touched`, the slots the program's write-backs reached, settles a first
    draw that lands on a slot's edge.  Returns the readings `compare` takes.
    """
    omega, eps = hp["priority_exponent"], hp["priority_eps"]
    grad = jax.jit(
        jax.value_and_grad(
            lambda p, t, b, k: loss_fn(p, t, b, k, hp, mode), has_aux=True))
    adam = jax.jit(functools.partial(
        nets.adam_step, lr=hp["learning_rate"], eps=hp["adam_eps"],
        clip=hp["max_grad_norm"]))
    params = jax.tree.map(jnp.asarray, params0)
    target = jax.tree.map(jnp.asarray, target0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    priority = np.array(priority0, np.float64)
    out = {"loss": [], "priority0": priority.copy()}
    for t, (k_sample, k_learn, beta) in enumerate(steps, start=1):
        batch, idx = sample(priority, k_sample, beta,
                            touched if t == 1 else None)
        (loss, aux), grads = grad(params, target, batch, k_learn)
        out["loss"].append(float(loss))
        written = (np.asarray(aux["priorities"], np.float64) + eps) ** omega
        if t == 1:
            out["idx1"] = idx
            gn, clip = float(nets.global_norm(grads)), hp["max_grad_norm"]
            scale = 1.0 if not clip > 0 or gn < clip else clip / gn
            out["grad1"] = jax.tree.map(
                lambda g: np.asarray(g, np.float64) * scale, grads)
        priority[idx] = np.where(priority[idx] > 0, written, 0.0)
        params, m, v = adam(params, grads, m, v, t)
    out["priority_after"] = priority
    out["params_after"] = jax.tree.map(np.asarray, params)
    return out


def settle_edges(idx, margin, priority, touched):
    """A draw within rounding of a slot's edge goes to whichever neighbour
    the program's write-back reached."""
    idx = idx.copy()
    if touched is None:
        return idx
    for r in np.nonzero(margin < AMBIGUOUS)[0]:
        for cand in (idx[r], idx[r] - 1, idx[r] + 1):
            if 0 <= cand < len(priority) and priority[cand] > 0 and touched[cand]:
                idx[r] = cand
                break
    return idx


def _flat(tree, base=None):
    """The leaves as float64 vectors, less `base`'s where given."""
    leaves = [np.asarray(x, np.float64).ravel() for x in jax.tree.leaves(tree)]
    if base is None:
        return leaves
    return [x - np.asarray(b, np.float64).ravel()
            for x, b in zip(leaves, jax.tree.leaves(base))]


def median_leaf_norm_gap(cand, ref):
    """The median leaf's |‖cand‖ - ‖ref‖| over the reference's norm of that
    leaf or of the median leaf, whichever is larger (some leaves are all but
    zero).  `cand`, `ref`: lists of vectors.  The median leaf and not the
    worst: the worst is a bias of three elements whose gap does not shrink
    with a small gradient (PERF.md, section 6)."""
    rn = [float(np.linalg.norm(x)) for x in ref]
    med = float(np.median(rn))
    return float(np.median([
        abs(float(np.linalg.norm(a)) - n) / max(n, med, 1e-30)
        for a, n in zip(cand, rn)]))


def median_leaf_angle(cand, ref):
    """1 - cosine between the two first gradients (as the optimizer gets
    them, after the global-norm clip), leaf by leaf: the median over the
    leaves no smaller than the median leaf.  Blind to a common scale and
    sharp on rounding noise; steady from seed to seed where the worst leaf's
    is not."""
    c, r = _flat(cand), _flat(ref)
    rn = [float(np.linalg.norm(x)) for x in r]
    med = float(np.median(rn))
    return float(np.median([
        1.0 - float(a @ b) / max(float(np.linalg.norm(a)) * n, 1e-30)
        for a, b, n in zip(c, r, rn) if n >= med]))


def first_gradient_by_leaf(cand, ref):
    """{leaf path: (reference norm, norm gap, 1 - cosine)}: what the two
    gradient's numbers above are the medians of, for looking into a seed that
    reads far off (benchmarks/tools/calibrate.py --leaves)."""
    out = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(cand),
                            jax.tree.leaves(ref)):
        a = np.asarray(a, np.float64).ravel()
        b = np.asarray(b, np.float64).ravel()
        na, nb = float(np.linalg.norm(a)), float(np.linalg.norm(b))
        out[jax.tree_util.keystr(path)] = (
            nb, abs(na - nb), 1.0 - float(a @ b) / max(na * nb, 1e-30))
    return out


def compare(cand, ref, params0):
    """{name: value} of every number compared; `cand` is the program's side
    (or a control's), `ref` the float32 reference's."""
    idx1 = ref["idx1"]
    touched = np.asarray(cand["priority_after"]) != ref["priority0"]
    numbers = {
        "draw_misses": float(np.sum(~touched[idx1])),
        "loss1_rel": abs(cand["loss"][0] - ref["loss"][0])
        / max(abs(ref["loss"][0]), 1e-30),
        "dparam_median_gap": median_leaf_norm_gap(
            _flat(cand["params_after"], params0),
            _flat(ref["params_after"], params0)),
    }
    if cand.get("grad1") is not None:
        numbers["grad1_median_angle"] = median_leaf_angle(
            cand["grad1"], ref["grad1"])
    return numbers


def verdict(numbers, limits, read_only=()):
    """(correct, [(name, value, limit)]) — every number beside its limit; a
    number without a limit, or a limit without its number, is not correct.
    `read_only` names the numbers a cell reads and does not compare (its
    file's `read_not_compared`: no control and no fault gives them an upper
    reading, so a limit could only fail sound runs); they may have no limit."""
    if set(read_only) & set(limits):
        raise ValueError("a number is compared or read only, not both")
    numbers = {n: v for n, v in numbers.items() if n not in read_only}
    rows = [(n, numbers.get(n, float("nan")), limits.get(n, float("nan")))
            for n in sorted(set(numbers) | set(limits))]
    ok = all(np.isfinite(v) and np.isfinite(lim) and v <= lim
             for _, v, lim in rows)
    return bool(ok), rows
