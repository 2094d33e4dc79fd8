"""YCSB core workload A's key chooser driving a keyed CAS-register history:
every client draws each op's key from YCSB's scrambled Zipfian, so a few
records carry most of the ops, the concurrency and the crashes.

`shape['threads_per_key'] * shape['keys_in_flight']` clients (one group
that may take any key) run a closed loop: a random client either completes
its pending op or invokes a new one. An invoke draws its key as YCSB's
`CoreWorkload.nextKeynum` does with `requestdistribution=zipfian`: a
`ScrambledZipfianGenerator` over `records` records, which is a
`ZipfianGenerator` over items [0, 10^10] at the constant 0.99 with YCSB's
precomputed zeta, hashed onto `records + 1` slots by `Utils.fnvhash64`;
a key not yet inserted (the last slot) is drawn again. The op is drawn from
`shape['mix']`; a write's value is uniform over the register's values.

Every op takes effect at its invocation, so the history is linearizable;
each write crashes :info by a coin of odds `crash.rate`, landing or not as
`crash.effect` says, and the crashed client's process is replaced.
`corrupt_key_share` of the keys get one read rewritten by
`register.corrupt`.

Everything is drawn from the seed. Ops are plain dicts (type, f, value,
process, time, index) whose values are (key, value) pairs.
"""

from __future__ import annotations

import random

from benchmark.generators import register

ITEMS = 10 ** 10 + 1        # ScrambledZipfianGenerator's [0, ITEM_COUNT]
ZETAN = 26.46902820178302   # zeta(ITEMS, 0.99), as YCSB precomputes it
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
_MASK = (1 << 64) - 1


def fnvhash64(val):
    """YCSB's `Utils.fnvhash64`: FNV-1a 64 over the 8 bytes of `val`, least
    significant first, then Java's `Math.abs` of the signed result (which
    leaves -2^63 as it is)."""
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        h = ((h ^ (val & 0xFF)) * FNV_PRIME_64) & _MASK
        val >>= 8
    if h >= 1 << 63:
        h -= 1 << 64
    return h if h == -(1 << 63) else abs(h)


class Zipfian:
    """YCSB's `ZipfianGenerator(0, ITEM_COUNT, theta, ZETAN)`: `next(u)`
    maps a uniform draw in [0, 1) to an item, the most popular first."""

    def __init__(self, theta):
        if theta != 0.99:
            raise ValueError("YCSB's precomputed zeta holds for 0.99 only")
        self.alpha = 1.0 / (1.0 - theta)
        self.zeta2 = 1.0 + 0.5 ** theta
        self.eta = (1 - (2.0 / ITEMS) ** (1 - theta)) / (
            1 - self.zeta2 / ZETAN)

    def next(self, u):
        uz = u * ZETAN
        if uz < 1.0:
            return 0
        if uz < self.zeta2:
            return 1
        return int(ITEMS * (self.eta * u - self.eta + 1) ** self.alpha)


class KeyChooser:
    """`CoreWorkload.nextKeynum` over `records` inserted records."""

    def __init__(self, records, theta, rng):
        self.records = records
        self.zipf = Zipfian(theta)
        self.rng = rng

    def next(self):
        while True:
            key = fnvhash64(self.zipf.next(self.rng.random())) % (
                self.records + 1)
            if key < self.records:
                return key


def history(shape, params, rng):
    """One history as (ops, invocation count)."""
    n_threads = shape["threads_per_key"] * shape.get("keys_in_flight", 1)
    values = shape["values"]
    mix = shape["mix"]
    fs = sorted(mix)
    weights = [mix[f] for f in fs]
    crash = params.get("crash", {})
    if shape["distribution"] != "scrambled-zipfian":
        raise ValueError("only YCSB's scrambled Zipfian is generated")
    keys = KeyChooser(shape["records"], shape["zipfian_constant"], rng)

    ops = []
    t = 0
    state = {}                         # key -> register value
    process = list(range(n_threads))
    pending = {}                       # thread -> completion op
    invocations = 0

    def tick():
        nonlocal t
        t += rng.randint(1, 10)
        return t

    while invocations < params["ops"] or pending:
        th = rng.randrange(n_threads)
        if th in pending:
            comp = pending.pop(th)
            comp["time"] = tick()
            ops.append(comp)
            continue
        if invocations >= params["ops"]:
            continue
        key = keys.next()
        f = rng.choices(fs, weights)[0]
        p = process[th]
        crashes = f == "write" and rng.random() < crash.get("rate", 0.0)
        applied = not crashes or crash.get("effect") == "applied" or \
            (crash.get("effect") == "either" and rng.random() < 0.5)
        if f == "read":
            inv_v, comp_v = None, state.get(key)
        else:
            inv_v = comp_v = rng.randrange(values)
            if applied:
                state[key] = inv_v
        ops.append({"type": "invoke", "f": f, "value": (key, inv_v),
                    "process": p, "time": tick()})
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


def corruptible(ops, band):
    """The keys with an :ok read in the first `band` of their ops."""
    per_key = {}
    for o in ops:
        per_key.setdefault(o["value"][0], []).append(o)
    return sorted(k for k, h in per_key.items()
                  if any(o["type"] == "ok" and o["f"] == "read"
                         for o in h[:max(1, int(len(h) * band))]))


def pool(shape, params, seed):
    """The run's histories: `params['pool']` of them, each a dict with
    `ops`, `n_ops` (invocations) and `corrupted` (indices rewritten).
    `corrupt_key_share` of the records are corrupted, drawn in a seeded
    order among the keys `corruptible` names; a key whose reads near its
    start no rewrite can make fail is passed over for the next."""
    out = []
    band = params.get("corrupt_band", 0.05)
    want = round(params.get("corrupt_key_share", 0.0) * shape["records"])
    for h in range(params["pool"]):
        rng = random.Random(f"{seed}/{h}")
        ops, n = history(shape, params, rng)
        candidates = corruptible(ops, band)
        rng.shuffle(candidates)
        bad = []
        for key in candidates:
            if len(bad) == want:
                break
            try:
                bad.append(register.corrupt(ops, rng, shape["values"], band,
                                            key))
            except ValueError:
                continue
        out.append({"ops": ops, "n_ops": n, "corrupted": sorted(bad)})
    return out
