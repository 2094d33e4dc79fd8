"""The plain reference: linearizability of a CAS-register history, and of a
keyed history one key at a time, written from the definition and sharing
nothing with the program under test.

Semantics (Knossos' cas-register, the model both configurations state):
the register starts nil; a write sets it; cas(old, new) succeeds only from
old; a read of v is legal only from v, and a read of nil is legal from any
state. An operation that completed :ok took effect once, between its
invocation and its completion. A :fail operation took no effect. An
operation that crashed (:info, or no completion) took effect once at any
point after its invocation, or never; a crashed read constrains nothing.

The search walks the history's events in order with the set of
configurations (state, live ops already linearized, crashed ops used).
At a completion every configuration is closed under linearizing pending
ops, and those in which the completing op has not taken effect die. A
configuration that has used fewer crashed ops of each kind than another
with the same state and live set can do everything the other can, so only
the least such are kept. The history is linearizable iff a configuration
survives every completion.

`crashed="completed"` breaks the stated guarantee on purpose: a crashed op
is taken to have completed at its :info, so it must take effect before
then. That is the control of `correct` (PERF.md §2).
"""

from __future__ import annotations

NIL = None


def _step(state, f, value):
    """(legal, next state) of one op from `state`."""
    if f == "write":
        return True, value
    if f == "cas":
        old, new = value
        return (True, new) if state == old else (False, state)
    # read
    return (value is NIL or value == state), state


def _events(ops, crashed):
    """Lower a history to ('inv', slot, f, value) / ('ok', slot) /
    ('crash', kind) events. `kind` indexes the distinct (f, value) of
    crashed ops; a live op holds a slot from invocation to completion."""
    open_by_process = {}
    paired = {}                       # invocation position -> completion
    for i, o in enumerate(ops):
        t = o["type"]
        if t == "invoke":
            open_by_process[o["process"]] = i
        elif t in ("ok", "fail", "info"):
            j = open_by_process.pop(o["process"], None)
            if j is not None:
                paired[j] = i
    kinds = {}
    starts = {}                       # position -> event list
    for i, o in enumerate(ops):
        if o["type"] != "invoke":
            continue
        f = o["f"]
        j = paired.get(i)
        ctype = ops[j]["type"] if j is not None else "info"
        if ctype == "fail":
            continue
        if ctype == "info" and crashed == "completed" and j is not None:
            ctype = "ok"
        if ctype == "info":
            if f == "read":
                continue
            v = tuple(o["value"]) if f == "cas" else o["value"]
            k = kinds.setdefault((f, v), len(kinds))
            starts.setdefault(i, []).append(("crash", k))
            continue
        v = ops[j]["value"] if f == "read" else o["value"]
        if f == "cas":
            v = tuple(v)
        starts.setdefault(i, []).append(("inv", i, f, v))
        starts.setdefault(j, []).append(("ok", i))
    out = []
    for pos in sorted(starts):
        out.extend(starts[pos])
    return out, [fv for fv, _ in sorted(kinds.items(), key=lambda x: x[1])]


def _least(configs):
    """Keep, per (state, live set), the configurations whose crashed-op use
    no other one's use is below."""
    groups = {}
    for s, lin, used in configs:
        groups.setdefault((s, lin), []).append(used)
    out = set()
    for (s, lin), useds in groups.items():
        if len(useds) == 1:
            out.add((s, lin, useds[0]))
            continue
        useds.sort(key=sum)
        kept = []
        for u in useds:
            if not any(all(a <= b for a, b in zip(k, u)) for k in kept):
                kept.append(u)
        out.update((s, lin, u) for u in kept)
    return out


def _closure(configs, live, kinds, avail):
    """Every configuration reachable from `configs` by linearizing pending
    live ops and unused crashed ops, less those another one dominates.
    Configurations are expanded in order of crashed ops used, so one
    reached with fewer is always there to dominate one reached with
    more."""
    best = {}                          # (state, live set) -> [used, ...]
    levels = {}
    for c in configs:
        best.setdefault(c[:2], []).append(c[2])
        levels.setdefault(sum(c[2]), []).append(c)

    def add(c):
        useds = best.setdefault(c[:2], [])
        u = c[2]
        for k in useds:
            if all(a <= b for a, b in zip(k, u)):
                return
        useds[:] = [k for k in useds
                    if not all(a <= b for a, b in zip(u, k))]
        useds.append(u)
        levels.setdefault(sum(u), []).append(c)

    level = min(levels)
    while level in levels:
        stack = levels[level]
        while stack:
            s, lin, used = c = stack.pop()
            if used not in best[c[:2]]:
                continue               # dominated since it was queued
            for lb, (f, v) in live.items():
                if not lin & lb:
                    ok, s2 = _step(s, f, v)
                    if ok:
                        add((s2, lin | lb, used))
            for k, (f, v) in enumerate(kinds):
                if used[k] < avail[k]:
                    ok, s2 = _step(s, f, v)
                    if ok:
                        add((s2, lin, used[:k] + (used[k] + 1,)
                             + used[k + 1:]))
        del levels[level]
        level += 1
    return {(s, lin, u) for (s, lin), us in best.items() for u in us}


def linearizable(ops, crashed="any"):
    """True iff the single-register history `ops` (a list of op dicts with
    type, f, value, process) is linearizable."""
    events, kinds = _events(ops, crashed)
    n_kinds = len(kinds)
    avail = [0] * n_kinds
    slot_of = {}                      # invocation position -> bit
    free_bits = []
    next_bit = 0
    live = {}                         # bit -> (f, value)
    zero = (0,) * n_kinds
    configs = {(NIL, 0, zero)}
    for ev in events:
        tag = ev[0]
        if tag == "inv":
            _, pos, f, v = ev
            if free_bits:
                b = free_bits.pop()
            else:
                b, next_bit = 1 << next_bit, next_bit + 1
            slot_of[pos] = b
            live[b] = (f, v)
            continue
        if tag == "crash":
            k = ev[1]
            avail[k] += 1
            continue
        b = slot_of.pop(ev[1])
        seen = _closure(configs, live, kinds, avail)
        configs = _least((s, lin & ~b, used) for s, lin, used in seen
                         if lin & b)
        if not configs:
            return False
        del live[b]
        free_bits.append(b)
    return True


def key_histories(ops):
    """Split a keyed history (values are (key, value) pairs) into one
    history per key, in one pass, keys in order of first appearance."""
    per_key = {}
    for o in ops:
        k, v = o["value"]
        o = dict(o)
        o["value"] = v
        per_key.setdefault(k, []).append(o)
    return per_key


def linearizable_keyed(ops, crashed="any"):
    """{key: linearizable?} of a keyed history."""
    return {k: linearizable(h, crashed)
            for k, h in key_histories(ops).items()}
