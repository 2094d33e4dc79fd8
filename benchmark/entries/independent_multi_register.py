"""Entry: `independent.checker(checker.linearizable(multi_register()))`,
what jepsen-io/yugabyte's multi-key-acid workload checks. Values become
the program's (key, transaction) tuples, as a recorded keyed history
holds them."""

from __future__ import annotations

from benchmark import reference_multi_register as _reference
from benchmark.entries.independent import ALL, check, summary  # noqa: F401


def make():
    from jepsen_tpu import independent, models
    from jepsen_tpu.checker import wgl
    from jepsen_tpu.checker.linear import linearizable

    model = models.multi_register()
    if model.device_model not in wgl.DEVICE_MODELS:
        raise RuntimeError(
            "this program has no 'multi-register' device model: the "
            "cell's keys would all be searched on the host")
    return independent.checker(linearizable(model))


def fresh(ops, v):
    """A new history object over new ops, relabelled by the check's
    variant `v` (benchmark/relabel.py): each micro-op's value, keys,
    processes, times."""
    from jepsen_tpu.history import History
    from jepsen_tpu.independent import KV

    return History([{**o, "value": KV(v.key(o["value"][0]),
                                      [[f, k, v.value(x)]
                                       for f, k, x in o["value"][1]]),
                     "process": v.process(o["process"]),
                     "time": o["time"] + v.time_shift} for o in ops])


def reference(ops, crashed="any"):
    """The answers the plain reference gives, keyed as summary's."""
    per_key = _reference.linearizable_keyed(ops, crashed)
    return {ALL: all(per_key.values()), **per_key}
