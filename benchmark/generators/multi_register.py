"""Keyed multi-register histories in jepsen-io/yugabyte's multi-key-acid
shape, valid by construction unless corrupted on purpose.

Scheduled as `register.history` schedules a keyed register history: each
key (a row group) is worked by `threads_per_key` client threads, the
first `reserved_readers` of them reading and the rest drawing from
`shape['mix']`, with `keys_in_flight` groups at once, each claiming the
next key when its own is done. A key is done when a new process would
be its `process_limit`-th plus one, as Jepsen's `process-limit` ends
a key's generator. An op reads, or writes values in [0, values), over a
random non-empty subset of the `subkeys` subkeys, as multi_key_acid.clj's
generator draws them.

Every op takes effect at its invocation, a legal linearization point, so
the history is linearizable. Only writes crash: each by a coin of odds
`crash.rate`, landing or not as `crash.effect` says, completing :info,
and the crashed thread's process is replaced. A corrupted key has one
:ok read's micro-op value rewritten in its first `corrupt_band` of ops.

Everything is drawn from the seed: the same seed and parameters give the
same histories. Ops are plain dicts (type, f, value, process, time,
index) whose values are (key, transaction) pairs, a transaction being a
list of [f, subkey, value] micro-ops.
"""

from __future__ import annotations

import random

from benchmark import reference_multi_register as reference


def _subset(rng, n):
    ks = [k for k in range(n) if rng.random() < 0.5]
    return ks or [rng.randrange(n)]


def history(shape, params, rng):
    """One keyed history as (ops, invocation count)."""
    threads = shape["threads_per_key"]
    readers = shape.get("reserved_readers", 0)
    n_sub, values = shape["subkeys"], shape["values"]
    mix = shape["mix"]
    fs = sorted(mix)
    weights = [mix[f] for f in fs]
    limit = shape["process_limit"]
    groups = shape.get("keys_in_flight", 1)
    n_keys = params["keys"]
    crash = params.get("crash", {})
    n_threads = groups * threads

    ops = []
    t = 0
    state = {}                         # key -> {subkey: value}
    process = list(range(n_threads))
    pending = {}                       # thread -> completion op
    active = {}                        # group -> [key, processes, done]
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
        active[g] = [next_key, set(), False]
        state[next_key] = {}
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
        a = active.get(g)
        if a is None:
            continue
        key, procs, done = a
        if done:
            if not any(p // threads == g for p in pending):
                claim(g)
            continue
        p = process[th]
        if p not in procs and len(procs) >= limit:
            a[2] = True                # process limit: the key is done
            continue
        procs.add(p)
        f = "read" if th % threads < readers else \
            rng.choices(fs, weights)[0]
        regs = state[key]
        crashes = False
        if f == "read":
            sub = _subset(rng, n_sub)
            inv_v = [["r", k, None] for k in sub]
            comp_v = [["r", k, regs.get(k)] for k in sub]
        else:
            inv_v = [["w", k, rng.randrange(values)]
                     for k in _subset(rng, n_sub)]
            comp_v = [list(m) for m in inv_v]
            crashes = rng.random() < crash.get("rate", 0.0)
            applied = not crashes or crash.get("effect") == "applied" or \
                (crash.get("effect") == "either" and rng.random() < 0.5)
            if applied:
                for _, k, v in inv_v:
                    regs[k] = v
        inv = {"type": "invoke", "f": f, "value": (key, inv_v),
               "process": p, "time": tick()}
        ops.append(inv)
        invocations += 1
        comp = {"type": "ok", "f": f, "value": (key, comp_v), "process": p}
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


def corrupt(ops, rng, values, band, key):
    """Rewrite one micro-op of one :ok read of `key` among the first
    `band` of the key's ops to another value in the domain, so that every
    seed's corrupted key dies at about the same point. Among those reads,
    taken in a seeded order, and their micro-ops, the first value that
    leaves the key's prefix ending at the read non-linearizable, by the
    reference, is taken. Returns the rewritten op's index."""
    mine = [i for i, o in enumerate(ops) if o["value"][0] == key]
    head = mine[:max(1, int(len(mine) * band))]
    reads = [i for i in head
             if ops[i]["type"] == "ok" and ops[i]["f"] == "read"]
    rng.shuffle(reads)
    for i in reads:
        txn = ops[i]["value"][1]
        for m in rng.sample(range(len(txn)), len(txn)):
            news = [v for v in range(values) if v != txn[m][2]]
            rng.shuffle(news)
            for new in news:
                bad = [list(x) for x in txn]
                bad[m][2] = new
                prefix = [dict(ops[j], value=bad if j == i
                               else ops[j]["value"][1])
                          for j in mine if j <= i]
                if not reference.linearizable(prefix):
                    ops[i] = dict(ops[i], value=(key, bad))
                    return i
    raise ValueError("no read near the start can be made to fail")


def pool(shape, params, seed):
    """The run's histories: `params['pool']` of them, each a dict with
    `ops`, `n_ops` (invocations) and `corrupted` (indices rewritten)."""
    out = []
    band = params.get("corrupt_band", 0.05)
    for h in range(params["pool"]):
        rng = random.Random(f"{seed}/{h}")
        ops, n = history(shape, params, rng)
        share = params.get("corrupt_key_share", 0.0)
        bad = [corrupt(ops, rng, shape["values"], band, key)
               for key in sorted(rng.sample(range(params["keys"]),
                                            round(share * params["keys"])))]
        out.append({"ops": ops, "n_ops": n, "corrupted": bad})
    return out
