"""Each check of a run gets a history of its own.

A run cycles through a small pool of base histories. Every check, warm-up
included, checks its base history relabelled by maps that keep every
verdict: a permutation of the register's values (nil stays nil), of the
client threads (a process keeps its generation: p = thread + threads *
generation), and a shift of the times and, in a keyed history, of the
keys, so no two checks of a run share a key. A linearization of the base
history is one of the relabelled history under the same maps, and back,
so the reference's answers for the base history hold, key for key.

The n-th check takes the n-th of the value and thread permutations from a
seeded starting point, so no two checks of a run see the same history,
nor the same history up to its times or keys. Nothing the program keeps
from one check, such as a verdict cached by content or a per-key split,
can answer the next.
"""

from __future__ import annotations

import math
import random

TIME_SHIFT = 10 ** 9       # per check; the generator's times stay far below


def _perm(index, n):
    """The index-th permutation of range(n), by its factorial digits."""
    pool = list(range(n))
    out = []
    for k in range(n, 0, -1):
        index, d = divmod(index, k)
        out.append(pool.pop(d))
    return out


class Variant:
    """The maps of one check."""

    def __init__(self, values, threads, n_threads, key_shift, time_shift):
        self.values = values          # value -> value
        self.threads = threads        # thread -> thread
        self.n_threads = n_threads
        self.key_shift = key_shift
        self.time_shift = time_shift

    def value(self, v):
        if v is None:
            return None
        if isinstance(v, list):
            return [self.values[x] for x in v]
        return self.values[v]

    def process(self, p):
        g, t = divmod(p, self.n_threads)
        return self.threads[t] + self.n_threads * g

    def key(self, k):
        return k + self.key_shift

    def base_key(self, k):
        """The base history's key of a relabelled one."""
        return k - self.key_shift


class Relabels:
    """The variants of one run, drawn from its seed."""

    def __init__(self, seed, shape, traffic):
        self.n_values = shape["values"]
        self.n_threads = shape["threads_per_key"] * (
            shape.get("keys_in_flight", 1) if traffic.get("keys") else 1)
        self.keys = traffic.get("keys") or 0
        self.space = math.factorial(self.n_values) * math.factorial(
            self.n_threads)
        self.start = random.Random(f"{seed}/relabel").randrange(self.space)

    def variant(self, n):
        """The maps of the run's n-th check (0 is the first warm-up)."""
        vi, ti = divmod((self.start + n) % self.space,
                        math.factorial(self.n_threads))
        return Variant(_perm(vi, self.n_values), _perm(ti, self.n_threads),
                       self.n_threads, n * self.keys, n * TIME_SHIFT)
