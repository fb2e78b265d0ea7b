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

jax is imported only inside ``reduce_capture`` (for the .xplane.pb reader);
everything else is plain string and number work.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

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
LSTM_SCAN = "lstm_scan"  # the lax.scan over the LSTM cell (R2D2)
# ---- the Kimi-Linear and DeepSeek-V3 cores (models/mla_moe.py,
# models/kimi_linear.py)
CORE_EMBED = "core_embed"  # the input projection in the embedding's place
CORE_LAYER = "core_layer"  # one pre-norm block: mixer + feed-forward
KDA_SCAN = "kda_scan"  # the chunked delta-rule recurrence of a sequence
KDA_PREP = "kda_prep"  # inside it: the in-chunk preparation (WY factors)
MLA_PROJ = "mla_proj"  # q, kv_a with kv_norm, kv_b over [window; new], o
MLA_ATTN = "mla_attn"  # scores, mask, softmax, values over the latent window
MLA_ROPE = "mla_rope"  # inside it: the rope dimensions turned by their slot
MOE_ROUTE = "moe_route"  # router, top-k, the sort by held expert
MOE_EXPERTS = "moe_experts"  # gather, the grouped products, scatter-add
MOE_SHARED = "moe_shared"  # the shared expert
CORE_STEP = "core_step"  # the delta-rule recurrence of one step (the actor's tick)
# ---- the Qwen3-Next core's two mixers (models/qwen3_next.py); its scan wears
# KDA_SCAN / KDA_PREP / CORE_STEP, its expert layers the MOE_* names
GDN_MIX = "gdn_mix"  # Gated DeltaNet but its scan: projections, conv, gates, gated norm
GATTN_PROJ = "gattn_proj"  # q with its gate, k, v, their norms, o
GATTN_ATTN = "gattn_attn"  # scores, mask, softmax, values over the K/V window, the gate
GATTN_ROPE = "gattn_rope"  # inside it: the rotary dimensions turned by their slot
IQN_HEAD = "iqn_head"  # tau embedding + the tau-folded heads (IQN)
OPTIMIZER = "optimizer"  # tx.update, apply_updates, the target copy
GRAD_ALLREDUCE = "grad_allreduce"  # psum/pmax/pmean of the sharded builders

TICK_SCOPES = (TICK_ACT, TICK_ENV, TICK_APPEND, TICK_LEARN)
ALL_SCOPES = TICK_SCOPES + (
    REPLAY_DRAW, REPLAY_GATHER, REPLAY_WRITEBACK, LEARN_STEP, NET_TRUNK,
    LSTM_SCAN, IQN_HEAD, OPTIMIZER, GRAD_ALLREDUCE, CORE_LAYER, KDA_SCAN,
    KDA_PREP, MLA_ATTN, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, CORE_STEP,
    CORE_EMBED, MLA_PROJ, MLA_ROPE, NET_STEM, GDN_MIX, GATTN_PROJ, GATTN_ATTN,
    GATTN_ROPE,
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


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(
    r"\b(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\b(?:branch|called)_computations=\{([^}]*)\}")


def instruction_scopes(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """{instruction name: scope path} of a compiled module's text
    (``jitted.lower(...).compile().as_text()``), from each instruction's
    ``metadata={op_name=...}``.  An instruction the compiler made without
    metadata (a layout copy, a slice of a loop it unrolled) takes the path
    of the instruction that calls its computation: the copies inside the
    `while` that a gather became count as that gather's, and a copy in the
    entry computation, which nothing calls, has the empty path: it is
    known, and in no scope."""
    named: Dict[str, Tuple[str, ...]] = {}
    home: Dict[str, str] = {}  # instruction -> its computation
    caller: Dict[str, str] = {}  # computation -> the instruction calling it
    comp = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        inst = m.group(1)
        home[inst] = comp
        op = _OP_NAME.search(line)
        if op:
            named[inst] = scope_path(op.group(1))
        for one, many in _CALLS.findall(line):
            for called in [one] if one else re.findall(r"[\w.\-]+", many):
                caller.setdefault(called, inst)
    out = dict(named)
    for inst in home:
        seen, at = set(), inst
        while at not in named and at not in seen:
            seen.add(at)
            at = caller.get(home.get(at, ""), "")
        out[inst] = named.get(at, ())
    return out


def instruction_name(event_name: str) -> str:
    """The instruction an "XLA Ops" trace event belongs to: the event's name
    is the instruction's text, `%copy.284 = u8[...] copy(...)`, so the name
    is what stands before the first space or `=`, without the `%`."""
    return re.split(r"[ =]", event_name.lstrip("%"), maxsplit=1)[0]


def attribute(op_seconds: Iterable[Sequence],
              inst_scopes: Dict[str, Tuple[str, ...]]) -> dict:
    """Device self time by scope.  ``op_seconds`` is [(event name, self
    seconds)] as a trace reduction gives them.  Every entry lands in exactly
    one of three classes, which add up to the input:

      tick_s          its path holds a ``tick_*`` scope; it then counts in
                      ``by_scope`` under EVERY scope of its path (nested
                      scopes overlap: ``lstm_scan`` is part of ``learn_step``
                      is part of ``tick_learn``) and once in ``by_path``
                      under the whole path, "/"-joined (``seconds`` reads it)
      outside_tick_s  known instruction, no ``tick_*`` scope in its path
                      (``outside`` lists them, longest first)
      unresolved_s    the instruction is not in ``inst_scopes``: the
                      attribution is broken to that extent
                      (``unresolved`` lists them)
    """
    by_scope: Dict[str, float] = defaultdict(float)
    by_path: Dict[str, float] = defaultdict(float)
    outside: Dict[str, float] = defaultdict(float)
    unresolved: Dict[str, float] = defaultdict(float)
    total = tick_s = 0.0
    for name, seconds in op_seconds:
        seconds = float(seconds)
        total += seconds
        inst = instruction_name(name)
        path = inst_scopes.get(inst)
        if path is None:
            unresolved[inst] += seconds
        elif not any(s in TICK_SCOPES for s in path):
            outside[inst] += seconds
        else:
            tick_s += seconds
            by_path["/".join(path)] += seconds
            for scope in set(path):
                by_scope[scope] += seconds
    ranked = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "total_s": total,
        "tick_s": tick_s,
        "outside_tick_s": sum(outside.values()),
        "unresolved_s": sum(unresolved.values()),
        "by_scope": dict(by_scope),
        "by_path": dict(by_path),
        "outside": ranked(outside),
        "unresolved": ranked(unresolved),
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
    """[(name, start, end, self_ns, is_leaf)] of one line's (name, start,
    end) events, which nest: an event's self time is its own without what it
    contains."""
    out, stack = [], []  # stack entries: [name, start, end, child_ns, kids]

    def close(top):
        out.append((top[0], top[1], top[2], top[2] - top[1] - top[3],
                    top[4] == 0))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
            stack[-1][4] += 1
        stack.append([name, s, e, 0.0, 0])
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
    knows, matched to the "XLA Modules" events by name, and an operation of
    any other program counts as unresolved.  Each idle gap longer than 1 ms
    is put down to the innermost host event named in `span_names`
    (obs/trace.Tracer's spans are `TraceAnnotation`s, on the same clock)
    that covers its middle."""
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
    known = {module_name(t) for t in module_texts}
    inst_scopes: Dict[str, Tuple[str, ...]] = {}
    for text in module_texts:
        inst_scopes.update(instruction_scopes(text))
    lo = min(s for p in planes for _n, s, _e in ops[p])
    hi = max(e for p in planes for _n, _s, e in ops[p])
    busy_ns, gaps = 0.0, []
    op_s: Dict[str, float] = defaultdict(float)
    programs: Dict[str, Dict[str, float]] = {}
    for p in planes:
        mods = sorted(runs[p], key=lambda r: r[1])
        starts = [r[1] for r in mods]
        for name, s, e in mods:
            prog = programs.setdefault(name, {"runs": 0, "device_ms": 0.0})
            prog["runs"] += 1 / len(planes)
            prog["device_ms"] += (e - s) / 1e6 / len(planes)
        cur = lo
        for name, s, e, self_ns, leaf in sorted(
                _self_times(ops[p]), key=lambda ev: ev[1]):
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and s < mods[i][2] else "no program"
            # instruction names are unique within one module only: under the
            # program's name an op of another program resolves to nothing
            op_s[name if mod in known else f"{mod}:{name}"] += (
                self_ns / 1e9 / len(planes))
            if not leaf:
                continue
            if s - cur > MIN_GAP_NS:
                gaps.append((s - cur, _covering(host_spans, 0.5 * (s + cur))))
            busy_ns += max(e - max(s, cur), 0.0)
            cur = max(cur, e)
    attr = attribute(op_s.items(), inst_scopes)
    by_span: Dict[str, float] = defaultdict(float)
    for ns, span in gaps:
        by_span[span] += ns / 1e6
    busy_s, window_s = busy_ns / len(planes) / 1e9, (hi - lo) / 1e9
    return {
        "chips": len(planes),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 100.0 * (1.0 - busy_s / window_s) if window_s else 0.0,
        "programs": programs,
        "scoped_instructions": sum(1 for path in inst_scopes.values() if path),
        "dispatches": max(
            (programs[m]["runs"] for m in known if m in programs), default=0),
        **attr,
        "idle_gaps": [{"ms": ns / 1e6, "span": span}
                      for ns, span in sorted(gaps, reverse=True)[:32]],
        "idle_gap_ms_by_span": dict(by_span),
    }
