"""Names for the work inside a compiled program, and device time by name.

The fused trainers compile a whole segment (64 ticks of act -> env -> append
-> gated learn) into one XLA program, so a profiler trace of it is a list of
compiler names (`%copy.284`, `%fusion.403`) that change with every change to
the graph.  This module is the one place that says what those are:

  * the scope names below are what the program wraps its work in
    (``with jax.named_scope(device_scopes.REPLAY_DRAW):``).  They are a
    contract with the benchmark's readers (benchmarks/readers/) and with
    PERF.md: a refactor moves the ``with`` and keeps the name.
    ``jax.named_scope`` only writes ``op_name`` metadata; the compiled
    program is the same with and without it.
  * ``scope_path`` reads the scopes out of one ``op_name``,
    ``instruction_scopes`` out of a compiled module's text, and ``attribute``
    puts a trace's per-operation self times down to them.
  * ``reduce_capture`` does all of that for a ``--trace-dir`` capture
    (obs/trace.TraceWindow logs the result as one ``device_time`` row).

A fusion is attributed to the ONE ``op_name`` XLA gave it (the fusion's
root as a rule): where the compiler fuses the tail of one scope into the
head of the next, the whole fusion counts under the one that named it.  A
container (`while`, `conditional`) counts its own self time, which is the
waits between its children, under its own scope: the waits between the ops
of a tick land on the segment's outer ``while``, outside every tick scope.
The same waits are counted a second time where they belong, as idle: every
gap between two innermost ops of one program run goes to the scope path of
the op that ends it (``idle_s_by_path``), so busy and idle time together
account for the whole traced window by scope.  An instruction the compiler
made (no ``op_name`` of its own: a layout copy, a slice of a loop it
unrolled) is a class of its own beside its inherited scope
(``instruction_origins``): it is also put down to the instruction that
consumes its result, the one that needed the layout.  Where the compiler
writes a label of its own over the program's name (the TPU compiler lowers
`jax.lax.ragged_dot` to custom calls whose ``op_name`` is `ragged-dot-none`:
a name without a `/`, which no name stack of the program is), the
instruction counts under the scope path of what feeds it: the grouped
products of an expert layer, forwards and backwards, are `moe_experts`' time.

jax is imported only inside ``reduce_capture`` (for the .xplane.pb reader);
everything else is plain string and number work.
"""

from __future__ import annotations

import bisect
import functools
import glob
import heapq
import itertools
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

# ---- the fused tick (train_anakin.build_fused_segment,
# train_anakin_r2d2.build_fused_r2d2_segment)
TICK_ACT = "tick_act"  # shift_stack + the act step
TICK_ENV = "tick_env"  # env_step
TICK_APPEND = "tick_append"  # replay append (also the shard_map'd one)
TICK_LEARN = "tick_learn"  # the lax.cond with its scan over learn_fn
# ---- the HBM rings (replay/device.py, replay/device_sequence.py)
REPLAY_DRAW = "replay_draw"  # cumsum + searchsorted
REPLAY_GATHER = "replay_gather"  # row gathers + IS weights
REPLAY_WRITEBACK = "replay_writeback"  # the priority scatter
# ---- the learn step (ops/learn.py, ops/r2d2.py) and the networks
LEARN_STEP = "learn_step"  # forward, loss, backward, optimizer, target copy
NET_TRUNK = "net_trunk"  # conv trunk
NET_STEM = "net_stem"  # inside it: the first conv with its input side
LSTM_SCAN = "lstm_scan"  # the LSTM core (R2D2): the input product, the lax.scan
LSTM_INPUT = "lstm_input"  # inside it: x @ W_x over all T steps, outside the loop
# ---- the Kimi-Linear and DeepSeek-V3 cores (models/mla_moe.py,
# models/kimi_linear.py)
CORE_EMBED = "core_embed"  # the input projection in the embedding's place
CORE_LAYER = "core_layer"  # one block: mixer + feed-forward, each behind its
# pre-norm and, in a family that has them, before a norm of its output
KDA_SCAN = "kda_scan"  # the chunked delta-rule recurrence of a sequence
KDA_PREP = "kda_prep"  # inside it: the in-chunk preparation (WY factors)
MLA_PROJ = "mla_proj"  # q, kv_a with kv_norm, kv_b over the slots attended, o
MLA_ATTN = "mla_attn"  # scores, mask, softmax, values over the latent window
MLA_ROPE = "mla_rope"  # inside it: the rope dimensions turned by their slot
MOE_ROUTE = "moe_route"  # router, top-k, the sort by held expert
MOE_EXPERTS = "moe_experts"  # gather, the grouped products, scatter-add
MOE_SHARED = "moe_shared"  # the shared expert
CORE_STEP = "core_step"  # the delta-rule recurrence of one step (the actor's tick)
CORE_NORM = "core_norm"  # a block's norms (two, or four with the output
# norms), and the stack's final norm after every pass
DENSE_FFN = "dense_ffn"  # the SwiGLU of a dense (not expert) layer
KDA_MIX = "kda_mix"  # KDA but its scan: projections, conv, gates, o_norm, o
# ---- the Qwen3-Next core's two mixers (models/qwen3_next.py); its scan wears
# KDA_SCAN / KDA_PREP / CORE_STEP, its expert layers the MOE_* names
GDN_MIX = "gdn_mix"  # Gated DeltaNet but its scan: projections, conv, gates, gated norm
GATTN_PROJ = "gattn_proj"  # q with its gate, k, v, their norms, o
GATTN_ATTN = "gattn_attn"  # scores, mask, softmax, values over the K/V window, the gate
GATTN_ROPE = "gattn_rope"  # inside it: the rotary dimensions turned by their slot
# ---- a stack run several times over shared weights, and the Ouro core's
# mixer (models/mla_moe.py::_Stack, models/ouro.py)
LOOP_PASS = "loop_pass"  # one pass of a stack run several times: its layers, its final norm
MHA_PROJ = "mha_proj"  # plain multi-head attention: q, k, v, o (and the q/k
# norms of a family that has them)
MHA_ATTN = "mha_attn"  # scores, mask, softmax, values over the K/V window
MHA_ROPE = "mha_rope"  # inside it: every q and k head turned whole by its slot
# ---- the LFM2 core's gated short convolution (models/lfm2.py); its attention
# layer wears the MHA_* names (the q/k norms under MHA_PROJ), its expert
# layers MOE_ROUTE and MOE_EXPERTS (there is no shared expert)
SCONV_MIX = "sconv_mix"  # the input product, the two gates, the 3-tap
# convolution, the output product
# ---- the Laguna core's two kinds of attention layer (models/laguna.py):
# each wears its kind round the MHA_* names (the gate's projection under
# MHA_PROJ, its product under MHA_ATTN), its expert layers the MOE_* names
ATTN_SLIDING = "attn_sliding"  # a sliding-window layer's mixer, whole
ATTN_FULL = "attn_full"  # a full-attention layer's mixer, whole
IQN_HEAD = "iqn_head"  # tau embedding + the tau-folded heads (IQN)
OPTIMIZER = "optimizer"  # tx.update, apply_updates, the target copy
GRAD_ALLREDUCE = "grad_allreduce"  # psum/pmax/pmean of the sharded builders

TICK_SCOPES = (TICK_ACT, TICK_ENV, TICK_APPEND, TICK_LEARN)
ALL_SCOPES = TICK_SCOPES + (
    REPLAY_DRAW, REPLAY_GATHER, REPLAY_WRITEBACK, LEARN_STEP, NET_TRUNK,
    LSTM_SCAN, IQN_HEAD, OPTIMIZER, GRAD_ALLREDUCE, CORE_LAYER, KDA_SCAN,
    KDA_PREP, MLA_ATTN, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, CORE_STEP,
    CORE_EMBED, MLA_PROJ, MLA_ROPE, NET_STEM, GDN_MIX, GATTN_PROJ, GATTN_ATTN,
    GATTN_ROPE, CORE_NORM, DENSE_FFN, KDA_MIX, LSTM_INPUT, LOOP_PASS,
    MHA_PROJ, MHA_ATTN, MHA_ROPE, SCONV_MIX, ATTN_SLIDING, ATTN_FULL,
)
_KNOWN = frozenset(ALL_SCOPES)

# autodiff and batching wrap a scope's name (`transpose(jvp(lstm_scan))`);
# `jit(f)` names a function and is no scope
_WRAPPED = re.compile(r"^(?!p?jit\()[A-Za-z_]\w*\((.*)\)$", re.S)


def _split(path: str) -> List[str]:
    """`path` cut at the slashes that stand outside every parenthesis."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth <= 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def scope_path(op_name: str) -> Tuple[str, ...]:
    """Every known scope in an ``op_name``, outermost first, seen through
    ``jvp(...)``, ``transpose(...)`` and the like:
    ``.../tick_learn/learn_step/transpose(jvp(lstm_scan))/mul`` gives
    ``("tick_learn", "learn_step", "lstm_scan")``."""
    out: List[str] = []
    for part in _split(op_name):
        if part in _KNOWN:
            out.append(part)
            continue
        m = _WRAPPED.match(part)
        if m:
            out.extend(scope_path(m.group(1)))
    return tuple(out)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s+")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_SHAPE_OPCODE = re.compile(r"(.*?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(
    r"\b(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\b(?:branch|called)_computations=\{([^}]*)\}")

NO_SCOPE = "(no scope)"  # the key of an empty scope path where paths are keys
UNRESOLVED = "(unresolved)"  # of an op whose instruction no module text holds


class Origin(NamedTuple):
    """Where an instruction of a compiled module comes from."""

    own: bool  # it carries `op_name` metadata of its own: the program wrote it
    opcode: str  # `copy`, `fusion`, `while`, ...
    shape: str  # its result's shape as the text has it, layout and all
    consumer_path: Tuple[str, ...]  # see `instruction_origins`


@functools.lru_cache(maxsize=2)
def _parse(hlo_text: str):
    """One pass over a compiled module's text: ``[(instruction, computation,
    shape, opcode, op_name or None, operands)]`` in the text's order, and
    ``{computation: the first instruction that calls it}``.  Operands are the
    `%names` between the opcode and the metadata, so they also hold the
    computations an instruction calls; `instruction_origins` keeps those of
    the instruction's own computation."""
    rows, caller, comp = [], {}, ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        inst, rest = m.group(1), line[m.end():]
        so = _SHAPE_OPCODE.match(rest)
        shape, opcode = (so.group(1), so.group(2)) if so else ("", "")
        op = _OP_NAME.search(rest)
        cut = rest.find(", metadata={")
        body = rest[so.end() if so else 0: cut if cut >= 0 else len(rest)]
        operands = re.findall(r"%([\w.\-]+)", body) if "%" in body else \
            re.findall(r"[A-Za-z_][\w.\-]*", body)
        rows.append((inst, comp, shape, opcode,
                     op.group(1) if op else None, operands))
        for one, many in _CALLS.findall(rest):
            for called in [one] if one else re.findall(r"[\w.\-]+", many):
                caller.setdefault(called, inst)
    return rows, caller


def _first_readers(rows) -> Dict[str, str]:
    """{instruction: the first instruction of its own computation that reads
    its result}, in the text's order."""
    home = {row[0]: row[1] for row in rows}
    reader: Dict[str, str] = {}
    for inst, comp, _s, _o, _n, operands in rows:
        for operand in operands:
            if home.get(operand) == comp and operand != inst:
                reader.setdefault(operand, inst)
    return reader


def _labelled_paths(rows, named) -> Dict[str, Tuple[str, ...]]:
    """{instruction: scope path} of the instructions that bear the compiler's
    own label where a name of the program's stood (`instruction_scopes` says
    which and why): the path of the instructions that make its operands,
    each looked for through instructions without a name of the program's,
    and of several the innermost (the longest path: a grouped product's
    group sizes come from the learn step at large, its rows from the expert
    layer; of equals the last operand's, sizes standing before rows and
    kernels); where none has a name, of the first instruction that reads its
    result, through further ones without a name.  An instruction neither
    finds is left out."""
    home = {row[0]: row[1] for row in rows}
    feeds = {inst: [o for o in operands if home.get(o) == comp and o != inst]
             for inst, comp, _s, _o, _n, operands in rows}
    reader = _first_readers(rows)

    def follow(at, step):
        seen = set()
        while at is not None and at not in named and at not in seen:
            seen.add(at)
            at = step(at)
        return named.get(at)

    out = {}
    for inst, _c, _s, _o, op_name, _a in rows:
        if op_name is None or inst in named:
            continue
        paths = [follow(o, lambda at: (feeds[at] or [None])[0])
                 for o in feeds[inst]]
        paths = [p for p in paths if p is not None] or [
            follow(reader.get(inst), reader.get)]
        if paths[0] is not None:
            out[inst] = max(reversed(paths), key=len)
    return out


@functools.lru_cache(maxsize=2)  # a capture's readers ask again and again
def instruction_scopes(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """{instruction name: scope path} of a compiled module's text
    (``jitted.lower(...).compile().as_text()``), from each instruction's
    ``metadata={op_name=...}``.  An instruction the compiler made without
    metadata (a layout copy, a slice of a loop it unrolled) takes the path
    of the instruction that calls its computation: the copies inside the
    `while` that a gather became count as that gather's, and a copy in the
    entry computation, which nothing calls, has the empty path: it is
    known, and in no scope.  An instruction that bears the compiler's own
    label where the program's name stood (an ``op_name`` without a `/`,
    which no name stack of the program is: `ragged-dot-none` on the custom
    calls a `ragged_dot` becomes, forwards and backwards) takes the path of
    what feeds it (`_labelled_paths`), and where that finds nothing the path
    of its caller, as above.  The result is kept for the text's next asker:
    read it, do not change it."""
    rows, caller = _parse(hlo_text)
    named = {inst: scope_path(op_name)
             for inst, _c, _s, _o, op_name, _a in rows
             if op_name is not None and "/" in op_name}
    home = {row[0]: row[1] for row in rows}
    out = dict(named)
    if len(named) < sum(row[4] is not None for row in rows):
        out.update(_labelled_paths(rows, named))
    for inst in home:
        if inst in out:
            continue
        seen, at = set(), inst
        while at not in named and at not in seen:
            seen.add(at)
            at = caller.get(home.get(at, ""), "")
        out[inst] = named.get(at, ())
    return out


@functools.lru_cache(maxsize=2)
def instruction_origins(hlo_text: str) -> Dict[str, Origin]:
    """{instruction name: `Origin`} of the same text, by the same parse:
    whether the instruction carries ``op_name`` metadata of its own, its
    opcode and result shape, and its ``consumer_path``.  For an instruction
    with metadata that is its own scope path.  For one the compiler made it
    is the scope path of the first instruction of the same computation that
    reads its result, through further instructions without metadata (a copy
    read by a bitcast read by a fusion is that fusion's): a layout copy
    belongs to the op that needed the layout.  Where nothing with a name
    reads it (the computation's root) it keeps its inherited path, the one
    `instruction_scopes` gives it."""
    rows, _caller = _parse(hlo_text)
    inherited = instruction_scopes(hlo_text)
    own = {row[0]: row[4] is not None for row in rows}
    reader = _first_readers(rows)
    out = {}
    for inst, _c, shape, opcode, _n, _a in rows:
        seen, at = set(), inst
        while not own[at] and at not in seen and at in reader:
            seen.add(at)
            at = reader[at]
        out[inst] = Origin(own[inst], opcode, shape,
                           inherited[at if own[at] else inst])
    return out


def instruction_name(event_name: str) -> str:
    """The instruction an "XLA Ops" trace event belongs to: the event's name
    is the instruction's text, `%copy.284 = u8[...] copy(...)`, so the name
    is what stands before the first space or `=`, without the `%`."""
    return re.split(r"[ =]", event_name.lstrip("%"), maxsplit=1)[0]


def attribute(op_seconds: Iterable[Sequence],
              inst_scopes: Dict[str, Tuple[str, ...]],
              origins: Optional[Dict[str, Origin]] = None) -> dict:
    """Device self time by scope.  ``op_seconds`` is [(event name, self
    seconds)] as a trace reduction gives them.  Every entry lands in exactly
    one of three classes, which add up to the input:

      tick_s          its path holds a ``tick_*`` scope; it then counts in
                      ``by_scope`` under EVERY scope of its path (nested
                      scopes overlap: ``lstm_scan`` is part of ``learn_step``
                      is part of ``tick_learn``) and once in ``by_path``
                      under the whole path, "/"-joined (``seconds`` reads it)
      outside_tick_s  known instruction, no ``tick_*`` scope in its path
                      (``outside`` lists them, longest first; those with
                      a scope all the same, which is every op of a host-fed
                      loop's programs, are in ``outside_by_path``)
      unresolved_s    the instruction is not in ``inst_scopes``: the
                      attribution is broken to that extent
                      (``unresolved`` lists them)

    Across the three, with ``origins`` (`instruction_origins` of the same
    text), the instructions the compiler made are summed a second time:
    ``compiler_made_s``, ``compiler_made_by_consumer_path`` (the path of
    the instruction that reads the result, `NO_SCOPE` for the empty one) and
    ``compiler_made`` (instruction, seconds, opcode, shape, consumer path;
    longest first).  Without ``origins`` the three are None."""
    out = _attribute(
        (inst, s, inst_scopes.get(inst), origins.get(inst) if origins else None)
        for inst, s in ((instruction_name(n), s) for n, s in op_seconds))
    if origins is None:
        out.update(compiler_made_s=None, compiler_made_by_consumer_path=None,
                   compiler_made=None)
    return out


def path_key(path: Optional[Tuple[str, ...]]) -> str:
    """A scope path as a key: "/"-joined, `NO_SCOPE` for the empty path,
    `UNRESOLVED` for None (an instruction no module text holds)."""
    return UNRESOLVED if path is None else "/".join(path) or NO_SCOPE


def _attribute(entries) -> dict:
    """`attribute` over (instruction, seconds, path or None, `Origin` or
    None) entries: `reduce_events` resolves each op in its own program's
    text, where instruction names are unique."""
    by_scope: Dict[str, float] = defaultdict(float)
    by_path: Dict[str, float] = defaultdict(float)
    outside: Dict[str, float] = defaultdict(float)
    outside_by_path: Dict[str, float] = defaultdict(float)
    unresolved: Dict[str, float] = defaultdict(float)
    made: Dict[str, float] = defaultdict(float)
    made_by: Dict[str, float] = defaultdict(float)
    made_of: Dict[str, Origin] = {}
    total = tick_s = 0.0
    for inst, seconds, path, origin in entries:
        seconds = float(seconds)
        total += seconds
        if path is None:
            unresolved[inst] += seconds
        elif not any(s in TICK_SCOPES for s in path):
            outside[inst] += seconds
            if path:
                outside_by_path["/".join(path)] += seconds
        else:
            tick_s += seconds
            by_path["/".join(path)] += seconds
            for scope in set(path):
                by_scope[scope] += seconds
        if origin is not None and not origin.own:
            made[inst] += seconds
            made_by[path_key(origin.consumer_path)] += seconds
            made_of[inst] = origin
    ranked = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "total_s": total,
        "tick_s": tick_s,
        "outside_tick_s": sum(outside.values()),
        "unresolved_s": sum(unresolved.values()),
        "by_scope": dict(by_scope),
        "by_path": dict(by_path),
        "outside": ranked(outside),
        "outside_by_path": dict(outside_by_path),
        "unresolved": ranked(unresolved),
        "compiler_made_s": sum(made.values()),
        "compiler_made_by_consumer_path": dict(made_by),
        "compiler_made": [
            (inst, t, made_of[inst].opcode, made_of[inst].shape,
             path_key(made_of[inst].consumer_path))
            for inst, t in ranked(made)],
    }


def seconds(attribution: dict, *scopes: str) -> float:
    """Self time inside a tick of the ops whose path names ALL of `scopes`:
    ``seconds(a, LEARN_STEP, LSTM_SCAN)`` is the LSTM scan of the learn step
    without the one-step scan of the act tick."""
    return sum(t for path, t in attribution["by_path"].items()
               if set(scopes) <= set(path.split("/")))


# --------------------------------------------------------------------------
# from a profiler capture (--trace-dir) to one reduction
# --------------------------------------------------------------------------

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_NS = 1e6  # idle gaps longer than this are listed by host span


def load_capture(logdir: str) -> Optional[List[Tuple[str, str, str, float, float]]]:
    """[(plane, line, name, start_ns, duration_ns)] of the newest .xplane.pb
    under `logdir`, or None where there is none."""
    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        return None
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(max(paths, key=os.path.getmtime)).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def module_name(hlo_text: str) -> str:
    """`jit_segment` of a module text that starts `HloModule jit_segment,`:
    the "XLA Modules" events of its runs are named `jit_segment(<id>)`."""
    m = re.match(r"\s*HloModule\s+([^\s,]+)", hlo_text)
    return m.group(1) if m else ""


def _self_times(events):
    """[(name, start, end, self_ns, is_leaf, parent)] of one line's (name,
    start, end) events, which nest: an event's self time is its own without
    what it contains; `parent` is the name of the innermost event round it
    (a `while`, a `conditional`), "" at the top."""
    out, stack = [], []  # entries: [name, start, end, child_ns, kids, parent]

    def close(top):
        out.append((top[0], top[1], top[2], top[2] - top[1] - top[3],
                    top[4] == 0, top[5]))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
            stack[-1][4] += 1
        stack.append([name, s, e, 0.0, 0, stack[-1][0] if stack else ""])
    while stack:
        close(stack.pop())
    return out


def _covering(spans, t):
    """The innermost of the (name, start, end) spans that cover `t`."""
    inside = [sp for sp in spans if sp[1] <= t <= sp[2]]
    return min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "no span"


def reduce_events(events, module_texts: Sequence[str] = (),
                  span_names: Iterable[str] = ()) -> Optional[dict]:
    """What a capture says about the device, or None where it holds no device
    plane with an "XLA Ops" line (the CPU backend).

    Busy time is the union of the innermost operations of each device plane,
    averaged over the planes; an operation's time is its own (`_self_times`).
    Operations are put down to scopes (`attribute`) through the text of the
    module they ran in: `module_texts` are the compiled programs the caller
    knows, matched to the "XLA Modules" events by name (each op is looked up
    in its own program's text), and an operation of any other program counts
    as unresolved.

    Idle time is every gap between two innermost operations, of any length.
    A gap between two ops of ONE program run is idle inside a dispatch and
    goes to the scope path of the op that ends it, as that op's self time
    does (``idle_s_by_path``; `path_key` makes the keys); any other gap (no
    program running, or the next run's first op) is ``idle_between_
    dispatches_s``.  The two add up to ``window_s`` - ``busy_s`` exactly.
    ``idle_gaps`` lists the 32 longest gaps: one inside a dispatch with its
    path, the op that ends it (``op``), the op it follows (``after``) and
    the innermost container event round the closing op (`while.N`,
    `conditional.N`); every gap longer than 1 ms with the
    innermost host event named in `span_names` (obs/trace.Tracer's spans are
    `TraceAnnotation`s, on the same clock) that covers its middle, which
    ``idle_gap_ms_by_span`` sums."""
    span_names = set(span_names)
    ops, runs, host_spans = defaultdict(list), defaultdict(list), []
    for plane, line, name, s, d in events:
        if plane.startswith("/device:") and line == OPS_LINE:
            ops[plane].append((name, s, s + d))
        elif plane.startswith("/device:") and line == MODULES_LINE:
            runs[plane].append((name.split("(", 1)[0], s, s + d))
        elif name in span_names:
            host_spans.append((name, s, s + d))
    if not ops:
        return None
    planes = sorted(ops)
    scopes_of = {module_name(t): instruction_scopes(t) for t in module_texts}
    origins_of = {module_name(t): instruction_origins(t) for t in module_texts}
    lo = min(s for p in planes for _n, s, _e in ops[p])
    hi = max(e for p in planes for _n, _s, e in ops[p])
    busy_ns = between_ns = 0.0
    longest, long_gaps = [], []  # heap of the 32 longest; every gap over 1 ms
    order = itertools.count()  # ties never compare the dicts
    op_s: Dict[Tuple[str, str], float] = defaultdict(float)
    idle_by_path: Dict[str, float] = defaultdict(float)
    key_of: Dict[Tuple[str, str], str] = {}  # (program, event) -> path key
    programs: Dict[str, Dict[str, float]] = {}
    for p in planes:
        mods = sorted(runs[p], key=lambda r: r[1])
        starts = [r[1] for r in mods]
        for name, s, e in mods:
            prog = programs.setdefault(name, {"runs": 0, "device_ms": 0.0})
            prog["runs"] += 1 / len(planes)
            prog["device_ms"] += (e - s) / 1e6 / len(planes)
        cur, last_run, last = lo, None, ""  # the last innermost op, its run
        for name, s, e, self_ns, leaf, parent in sorted(
                _self_times(ops[p]), key=lambda ev: ev[1]):
            i = bisect.bisect_right(starts, s) - 1
            run = i if i >= 0 and s < mods[i][2] else None
            mod = mods[run][0] if run is not None else "no program"
            op_s[mod, name] += self_ns / 1e9 / len(planes)
            if not leaf:
                continue
            ns = s - cur
            if ns > 0:
                inside = run is not None and run == last_run
                if inside:
                    if (mod, name) not in key_of:
                        key_of[mod, name] = path_key(scopes_of.get(
                            mod, {}).get(instruction_name(name)))
                    idle_by_path[key_of[mod, name]] += ns / 1e9 / len(planes)
                else:
                    between_ns += ns
                if ns > MIN_GAP_NS or len(longest) < 32 or ns > longest[0][0]:
                    gap = {"ms": ns / 1e6}
                    if inside:
                        gap.update(path=key_of[mod, name],
                                   op=instruction_name(name),
                                   after=instruction_name(last),
                                   container=instruction_name(parent))
                    entry = (ns, next(order), 0.5 * (cur + s), gap)
                    if ns > MIN_GAP_NS:
                        long_gaps.append(entry)
                    elif len(longest) < 32:
                        heapq.heappush(longest, entry)
                    else:
                        heapq.heapreplace(longest, entry)
            busy_ns += max(e - max(s, cur), 0.0)
            if e >= cur:
                cur, last = e, name
            last_run = run
        between_ns += hi - cur  # a plane that ends before the last one
    single = len(scopes_of) == 1  # one program: its instructions' own names
    attr = _attribute(
        (inst if single and mod in scopes_of else f"{mod}:{inst}", t,
         scopes_of.get(mod, {}).get(inst), origins_of.get(mod, {}).get(inst))
        for (mod, name), t in op_s.items()
        for inst in [instruction_name(name)])
    by_span: Dict[str, float] = defaultdict(float)
    for _ns, _i, middle, gap in long_gaps:
        gap["span"] = _covering(host_spans, middle)
        by_span[gap["span"]] += gap["ms"]
    busy_s, window_s = busy_ns / len(planes) / 1e9, (hi - lo) / 1e9
    return {
        "chips": len(planes),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 100.0 * (1.0 - busy_s / window_s) if window_s else 0.0,
        "programs": programs,
        "scoped_instructions": sum(
            1 for paths in scopes_of.values() for path in paths.values()
            if path),
        "dispatches": max(
            (programs[m]["runs"] for m in scopes_of if m in programs),
            default=0),
        **attr,
        "idle_s_by_path": dict(idle_by_path),
        "idle_between_dispatches_s": between_ns / len(planes) / 1e9,
        "idle_gaps": [
            gap for *_k, gap in sorted(long_gaps + longest, reverse=True)[:32]],
        "idle_gap_ms_by_span": dict(by_span),
    }
