"""The one traffic generator: concurrent CAS-register histories, single or
keyed, valid by construction unless corrupted on purpose.

A copy, with the source's op mix made a parameter, of the program's
`jepsen_tpu.checker.synth.register_history` / `adversarial_register_history`
scheduler (PERF.md, Open questions: delete those for this one later).
Each key is worked by a group of client threads; `keys_in_flight` groups
run at once and claim the next key when theirs is done, as Jepsen's
`independent/concurrent-generator` does. Every op takes effect at its
invocation, which is a legal linearization point, so the history is
linearizable; a crashed op either took effect there or never, as its
`crash.effect` says, and completes :info. A corrupted history has one :ok
read rewritten to another value in the domain, which the reference may
or may not find explicable.

Everything is drawn from the seed: the same seed and parameters give the
same histories. Ops are plain dicts (type, f, value, process, time,
index); a keyed history's values are (key, value) pairs.
"""

from __future__ import annotations

import random

from benchmark import reference


def _crash_positions(rng, n_ops, crash):
    """Op numbers (within a key) at which a mutating op crashes."""
    count = crash.get("count")
    if count is None:
        return None
    if crash.get("placement") == "even":
        return {round((i + 1) * n_ops / (count + 1)) for i in range(count)}
    return set(rng.sample(range(n_ops), count))


class _Key:
    def __init__(self, key, n_ops, crash_at):
        self.key = key
        self.budget = n_ops
        self.emitted = 0
        self.crash_at = crash_at
        self.processes = set()
        self.pending_crash = False     # a read drew a crash slot: next
                                       # mutating op takes it


def history(shape, params, rng):
    """One history as (ops, invocation count)."""
    threads = shape["threads_per_key"]
    readers = shape.get("reserved_readers", 0)
    values = shape["values"]
    mix = shape["mix"]
    fs = sorted(mix)
    weights = [mix[f] for f in fs]
    limit = shape.get("process_limit")
    keyed = params.get("keys") is not None
    n_keys = params["keys"] if keyed else 1
    groups = shape.get("keys_in_flight", 1) if keyed else 1
    crash = params.get("crash", {})
    n_threads = groups * threads

    def key_budget():
        n = shape["ops_per_key"] if keyed else params["ops"]
        j = shape.get("ops_per_key_jitter", 0.0)
        return max(1, round((1 - j + rng.random() * 2 * j) * n)) if j else n

    ops = []
    t = 0
    state = {}                         # key -> register value
    process = list(range(n_threads))
    pending = {}                       # thread -> completion op
    active = {}                        # group -> _Key
    next_key = 0
    invocations = 0

    def tick():
        nonlocal t
        t += rng.randint(1, 10)
        return t

    def claim(g):
        nonlocal next_key
        if next_key >= n_keys:
            active.pop(g, None)
            return
        n = key_budget()
        active[g] = _Key(next_key, n, _crash_positions(rng, n, crash))
        state[next_key] = None
        next_key += 1

    for g in range(groups):
        claim(g)
    while active or pending:
        th = rng.randrange(n_threads)
        g = th // threads
        if th in pending:
            comp = pending.pop(th)
            comp["time"] = tick()
            ops.append(comp)
            continue
        k = active.get(g)
        if k is None:
            continue
        if k.emitted >= k.budget:
            if not any(p // threads == g for p in pending):
                claim(g)
            continue
        p = process[th]
        if limit is not None and p not in k.processes \
                and len(k.processes) >= limit:
            k.budget = k.emitted       # process limit: the key is done
            continue
        k.processes.add(p)
        f = "read" if th % threads < readers else \
            rng.choices(fs, weights)[0]
        crashes = False
        if k.crash_at is not None and crash.get("ops") == "write" \
                and k.emitted in k.crash_at:
            f, crashes = "write", True
        elif f != "read":
            if k.crash_at is None:
                crashes = rng.random() < crash.get("rate", 0.0)
            else:
                crashes = k.pending_crash or k.emitted in k.crash_at
                k.pending_crash = False
        elif k.crash_at is not None and k.emitted in k.crash_at:
            k.pending_crash = True     # reads never crash
        value = state[k.key]
        applied = not crashes or crash.get("effect") == "applied" or \
            (crash.get("effect") == "either" and rng.random() < 0.5)
        if f == "read":
            inv_v, comp_t, comp_v = None, "ok", value
        elif f == "write":
            inv_v = comp_v = rng.randrange(values)
            comp_t = "ok"
            if applied:
                state[k.key] = inv_v
        else:
            inv_v = comp_v = [rng.randrange(values), rng.randrange(values)]
            comp_t = "ok" if value == inv_v[0] else "fail"
            if comp_t == "ok" and applied:
                state[k.key] = inv_v[1]
        if keyed:
            inv_v, comp_v = (k.key, inv_v), (k.key, comp_v)
        inv = {"type": "invoke", "f": f, "value": inv_v, "process": p,
               "time": tick()}
        ops.append(inv)
        k.emitted += 1
        invocations += 1
        comp = {"type": comp_t, "f": f, "value": comp_v, "process": p}
        if crashes:
            comp["type"] = "info"
            comp["time"] = tick()
            ops.append(comp)
            process[th] = p + n_threads
        else:
            pending[th] = comp
    for i, o in enumerate(ops):
        o["index"] = i
    return ops, invocations


def corrupt(ops, rng, values, band, key=None):
    """Rewrite one :ok read (of `key`, in a keyed history) near the start
    to another value in the domain, so that every seed's corrupted
    history dies at about the same point. Among the reads in the first
    `band` of the (key's) ops, taken in a seeded order, the first value
    that leaves the prefix ending at the read non-linearizable, by the
    reference, is taken: a prefix that is not linearizable makes the
    whole history not linearizable. Returns the rewritten op's index."""
    mine = [i for i, o in enumerate(ops)
            if key is None or o["value"][0] == key]
    head = mine[:max(1, int(len(mine) * band))]
    reads = [i for i in head
             if ops[i]["type"] == "ok" and ops[i]["f"] == "read"]
    rng.shuffle(reads)
    for i in reads:
        old = ops[i]["value"][1] if key is not None else ops[i]["value"]
        news = [v for v in range(values) if v != old]
        rng.shuffle(news)
        for new in news:
            prefix = [ops[j] if j != i else
                      dict(ops[j], value=(key, new) if key is not None
                           else new)
                      for j in mine if j <= i]
            if key is not None:
                prefix = [dict(o, value=o["value"][1]) for o in prefix]
            if not reference.linearizable(prefix):
                ops[i] = dict(ops[i], value=(key, new) if key is not None
                              else new)
                return i
    raise ValueError("no read near the start can be made to fail")


def pool(shape, params, seed):
    """The run's histories: `params['pool']` of them, each a dict with
    `ops`, `n_ops` (invocations) and `corrupted` (indices rewritten)."""
    out = []
    every = params.get("corrupt_every")
    band = params.get("corrupt_band", 0.05)
    for h in range(params["pool"]):
        rng = random.Random(f"{seed}/{h}")
        ops, n = history(shape, params, rng)
        bad = []
        if params.get("keys") is not None:
            share = params.get("corrupt_key_share", 0.0)
            for key in sorted(rng.sample(range(params["keys"]),
                                         round(share * params["keys"]))):
                bad.append(corrupt(ops, rng, shape["values"], band, key))
        elif every and (h + 1) % every == 0:
            bad.append(corrupt(ops, rng, shape["values"], band))
        out.append({"ops": ops, "n_ops": n, "corrupted": bad})
    return out
