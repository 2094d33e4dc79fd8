"""The plain reference for multi-register histories: linearizability of a
history of transactions over a few registers, and of a keyed history one
key at a time, written from the definition and sharing nothing with the
program under test.

Semantics (Knossos' multi-register, as jepsen-io/yugabyte's
multi-key-acid checks it): every subkey starts nil. An operation's value
is a transaction, a list of micro-ops [f, k, v] applied in order: a
write ("w") sets subkey k to v; a read ("r") of v is legal only if k
holds v at that point, and a read of nil is legal from any state. An
operation that completed :ok took effect once, atomically, between its
invocation and its completion, with the values its completion reports.
A :fail operation took no effect. An operation that crashed (:info, or
no completion) took effect once at any point after its invocation, or
never; a crashed read constrains nothing.

A crashed write may take effect at any later point, so its configurations
multiply with every crash a key sees. The search therefore first lets
each crashed op take effect only until the next crash is invoked: a
linearization found so is one of the history, as it only narrows when
crashed ops may take effect. Only a history that fails so is searched
again with no such limit, whose verdict is the history's own.

The search walks the history's events in order with the set of
configurations (state, live ops already linearized, crashed ops used).
At a completion every configuration is closed under linearizing pending
ops, and those in which the completing op has not taken effect die. A
configuration that has used a subset of another's crashed ops (counted
per distinct transaction), with the same state and live set, can do
everything the other can, so only the least are kept. The history is
linearizable iff a configuration survives every completion.

`crashed="completed"` breaks the stated guarantee on purpose: a crashed
op is taken to have completed at its :info, so it must take effect
before then. That is the control of `correct` (PERF.md §2).
"""

from __future__ import annotations


def _txn(value):
    """A transaction as a hashable tuple of (f, k, v) micro-ops."""
    return tuple((f, k, v) for f, k, v in (value or ()))


def _step(state, txn):
    """(legal, next state) of one transaction from `state`, a sorted
    tuple of (subkey, value) pairs."""
    regs = dict(state)
    for f, k, v in txn:
        if f in ("r", "read"):
            if v is not None and regs.get(k) != v:
                return False, state
        else:
            regs[k] = v
    return True, tuple(sorted(regs.items()))


def _events(ops, crashed, window=False):
    """Lower a history to ('inv', slot, txn) / ('ok', slot) /
    ('crash', n) / ('expire', n) events, n numbering the crashed ops;
    with `window` each crashed op expires where the next is invoked. A
    live op holds a slot from invocation to completion."""
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
    crashes = []
    starts = {}                       # position -> event list
    for i, o in enumerate(ops):
        if o["type"] != "invoke":
            continue
        j = paired.get(i)
        ctype = ops[j]["type"] if j is not None else "info"
        if ctype == "fail":
            continue
        if ctype == "info" and crashed == "completed" and j is not None:
            ctype = "ok"
        if ctype == "info":
            txn = _txn(o["value"])
            if all(f in ("r", "read") for f, _, _ in txn):
                continue              # reads whose results never came
            if window and crashes:
                starts.setdefault(i, []).append(("expire", len(crashes) - 1))
            starts.setdefault(i, []).append(("crash", len(crashes)))
            crashes.append(txn)
            continue
        starts.setdefault(i, []).append(("inv", i, _txn(ops[j]["value"])))
        starts.setdefault(j, []).append(("ok", i))
    out = []
    for pos in sorted(starts):
        out.extend(starts[pos])
    return out, crashes


def _closure(configs, ops, fresh, step):
    """Every configuration reachable from `configs` by linearizing pending
    ops (`ops`: key -> (transaction, crashed?), a live op's key its bit in
    the live set, a crashed op's the negated bit it sets in `used`), less
    those another one dominates. `configs` are closed already under every
    op but the `fresh` ones, so they are expanded by those only, and what
    they reach by every op. Configurations are expanded in order of
    crashed ops used, so one reached with fewer is always there to
    dominate one reached with more.

    A read-only transaction leaves the state as it is, so a configuration
    that has taken a pending one where it is legal can do all that the
    configuration without it can: every configuration takes each such
    read at once, and those that have not are never kept."""
    best = {}                          # (state, live set) -> [used, ...]
    levels = {}
    reads = [(b, t) for b, (t, crashed) in ops.items()
             if not crashed and all(f in ("r", "read") for f, _, _ in t)]

    def saturate(s, lin):
        for b, t in reads:
            if not lin & b and step(s, t) is not None:
                lin |= b
        return lin

    for s, lin, used in configs:
        lin2 = saturate(s, lin)
        best.setdefault((s, lin2), []).append(used)
        levels.setdefault(used.bit_count(), []).append(
            ((s, lin2, used), lin2 == lin))
    every = [(-b if crashed else b, t, crashed)
             for b, (t, crashed) in ops.items()]
    some = [x for x, b in zip(every, ops) if b in fresh]

    def add(s, lin, u):
        lin = saturate(s, lin)
        useds = best.setdefault((s, lin), [])
        for k in useds:
            if not k & ~u:
                return
        useds[:] = [k for k in useds if u & ~k]
        useds.append(u)
        levels.setdefault(u.bit_count(), []).append(((s, lin, u), False))

    level = min(levels)
    while level in levels:
        stack = levels[level]
        while stack:
            c, old = stack.pop()
            s, lin, used = c
            if used not in best[c[:2]]:
                continue               # dominated since it was queued
            for b, txn, crashed in (some if old else every):
                if (used if crashed else lin) & b:
                    continue
                s2 = step(s, txn)
                if s2 is not None:
                    if crashed:
                        add(s2, lin, used | b)
                    else:
                        add(s2, lin | b, used)
        del levels[level]
        level += 1
    return {(s, lin, u) for (s, lin), us in best.items() for u in us}


def _least(configs):
    """Keep, per (state, live set), the configurations whose crashed-op use
    no other one's use is below."""
    groups = {}
    for s, lin, used in configs:
        groups.setdefault((s, lin), []).append(used)
    out = set()
    for (s, lin), useds in groups.items():
        useds.sort(key=int.bit_count)
        kept = []
        for u in useds:
            if all(k & ~u for k in kept):
                kept.append(u)
        out.update((s, lin, u) for u in kept)
    return out


def linearizable(ops, crashed="any"):
    """True iff the multi-register history `ops` (a list of op dicts with
    type, f, value, process; values are transactions) is linearizable."""
    return (crashed == "any" and _search(ops, crashed, window=True)) \
        or _search(ops, crashed)


def _search(ops, crashed, window=False):
    events, crashes = _events(ops, crashed, window)
    memo = {}

    def step(state, txn):
        """The state after `txn`, or None where it is illegal."""
        key = (state, txn)
        if key not in memo:
            ok, s2 = _step(state, txn)
            memo[key] = s2 if ok else None
        return memo[key]

    slot_of = {}                      # invocation position -> bit
    free_bits = []
    next_bit = 0
    pending = {}                      # bit -> (transaction, crashed?)
    crash_bit = [1 << i for i in range(len(crashes))]
    configs = {((), 0, 0)}
    fresh = set()                     # ops the configs are not closed under
    for ev in events:
        tag = ev[0]
        if tag == "inv":
            _, pos, txn = ev
            if free_bits:
                b = free_bits.pop()
            else:
                b, next_bit = 1 << next_bit, next_bit + 1
            slot_of[pos] = b
            pending[b] = (txn, False)
            fresh.add(b)
            continue
        if tag == "crash":
            b = -crash_bit[ev[1]]     # crashed ops' keys: negative bits
            pending[b] = (crashes[ev[1]], True)
            fresh.add(b)
            continue
        if tag == "expire":
            b = crash_bit[ev[1]]
            del pending[-b]
            configs = _least((s, lin, used & ~b) for s, lin, used in configs)
            continue
        b = slot_of.pop(ev[1])
        seen = _closure(configs, pending, fresh, step)
        configs = _least((s, lin & ~b, used) for s, lin, used in seen
                         if lin & b)
        if not configs:
            return False
        del pending[b]
        free_bits.append(b)
        fresh = set()
    return True


def key_histories(ops):
    """Split a keyed history (values are (key, transaction) pairs) into
    one history per key, in one pass, keys in order of first
    appearance."""
    per_key = {}
    for o in ops:
        k, v = o["value"]
        per_key.setdefault(k, []).append(dict(o, value=v))
    return per_key


def linearizable_keyed(ops, crashed="any"):
    """{key: linearizable?} of a keyed history."""
    return {k: linearizable(h, crashed)
            for k, h in key_histories(ops).items()}
