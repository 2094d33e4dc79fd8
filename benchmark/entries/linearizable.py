"""Entry: `checker.linearizable(cas_register())`, what `analyze` calls for
a single-register workload. The checker is the user's default: algorithm
"auto", device engine chosen by the program."""

from __future__ import annotations

from benchmark import reference as _reference

FLAGS = ("recovered", "degraded", "degraded-checkers", "device-fallback")


def make():
    from jepsen_tpu import models
    from jepsen_tpu.checker.linear import linearizable

    return linearizable(models.cas_register())


def fresh(ops, v):
    """A new history object over new ops, relabelled by the check's
    variant `v` (benchmark/relabel.py), so nothing the program keeps from
    one check carries to the next."""
    from jepsen_tpu.history import History

    return History([{**o, "value": v.value(o["value"]),
                     "process": v.process(o["process"]),
                     "time": o["time"] + v.time_shift} for o in ops])


def check(checker, hist):
    return checker.check({}, hist, {})


def summary(result, v):
    """(answers, analyzers, flags) of one result. The single answer's key
    is None."""
    return ({None: result.get("valid?")}, {str(result.get("analyzer"))},
            sorted(f for f in FLAGS if f in result))


def reference(ops, crashed="any"):
    """The answers the plain reference gives, keyed as summary's."""
    return {None: _reference.linearizable(ops, crashed)}
