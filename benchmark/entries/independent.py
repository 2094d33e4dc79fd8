"""Entry: `independent.checker(checker.linearizable(cas_register()))`, what
`analyze` calls for a keyed workload such as jepsen.tests.
linearizable-register. Values become the program's (key, value) tuples,
as a recorded keyed history holds them."""

from __future__ import annotations

from benchmark import reference as _reference

FLAGS = ("recovered", "degraded", "degraded-checkers", "device-fallback")
ALL = "all"     # the answer key of the history's own verdict


def make():
    from jepsen_tpu import independent, models
    from jepsen_tpu.checker.linear import linearizable

    return independent.checker(linearizable(models.cas_register()))


def fresh(ops, v):
    """A new history object over new ops, relabelled by the check's
    variant `v` (benchmark/relabel.py): keys, values, processes, times."""
    from jepsen_tpu.history import History
    from jepsen_tpu.independent import KV

    return History([{**o, "value": KV(v.key(o["value"][0]),
                                      v.value(o["value"][1])),
                     "process": v.process(o["process"]),
                     "time": o["time"] + v.time_shift} for o in ops])


def check(checker, hist):
    return checker.check({}, hist, {})


def summary(result, v):
    """(answers, analyzers, flags): one answer per key of the base
    history, plus the history's own under ALL."""
    answers = {ALL: result.get("valid?")}
    analyzers = set()
    flags = {f for f in FLAGS if f in result}
    for k, r in (result.get("results") or {}).items():
        r = r or {}
        answers[v.base_key(k)] = r.get("valid?")
        analyzers.add(str(r.get("analyzer")))
        flags.update(f for f in FLAGS if f in r)
    return answers, analyzers, sorted(flags)


def reference(ops, crashed="any"):
    """The answers the plain reference gives, keyed as summary's."""
    per_key = _reference.linearizable_keyed(ops, crashed)
    return {ALL: all(per_key.values()), **per_key}
