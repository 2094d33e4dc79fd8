"""Performance tier, mirroring the reference's :perf-tagged tests
(`jepsen/test/jepsen/perf_test.clj`; the >20k ops/s single-thread
claim at `generator.clj:66-70`).

Thresholds sit well under the measured numbers (~31k pure-generator,
~15k full interpreter on an unloaded box) so a loaded CI box doesn't
flake, while a 3x regression still fails."""

import os
import random
import time

import pytest

from jepsen_tpu import core, testkit
from jepsen_tpu import generator as gen
from jepsen_tpu.generator import simulate
import jepsen_tpu.checker


def _mixed_gen(n):
    rng = random.Random(45100)
    return gen.clients(gen.limit(n, gen.mix([
        lambda: {"f": "read"},
        lambda: {"f": "write", "value": rng.randint(0, 4)},
    ])))


@pytest.mark.perf
def test_pure_generator_throughput():
    """Reference parity: >20k ops/s from the pure generator stack,
    single-threaded (`generator.clj:66-70`)."""
    n = 50_000
    ctx = gen.context({"concurrency": 10})
    t0 = time.monotonic()
    h = simulate.quick(ctx, _mixed_gen(n))
    rate = n / (time.monotonic() - t0)
    assert len(h) == n
    print(f"pure generator: {rate:.0f} ops/s")
    assert rate > 12_000, f"generator too slow: {rate:.0f} ops/s"


@pytest.mark.perf
def test_interpreter_throughput(tmp_path):
    """Full round-trip: scheduler + worker threads + per-thread inboxes +
    atom client + history journaling.

    The run is held to one CPU. Every op hands the interpreter lock
    between the scheduler and a worker thread; on a virtual machine a
    thread asleep on another vCPU can take longer to wake than the op
    takes, and that wait measures the machine's load, not this code
    (on an 8-vCPU VM the run went 2-3x faster held to one CPU)."""
    state = testkit.AtomState()
    n = 20_000
    t = testkit.noop_test()
    t.update({
        "name": "perf", "ssh": {"dummy": True},
        "store-dir": str(tmp_path / "store"), "concurrency": 10,
        "db": testkit.atom_db(state),
        "client": testkit.atom_client(state, latency_s=0.0),
        "generator": _mixed_gen(n),
        "checker": jepsen_tpu.checker.unbridled_optimism(),
    })
    cpus = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, {cpus[os.getpid() % len(cpus)]})
    try:
        t0 = time.monotonic()
        done = core.run(t)
        rate = n / (time.monotonic() - t0)
    finally:
        os.sched_setaffinity(0, cpus)
    invokes = sum(1 for o in done["history"]
                  if o.get("type") == "invoke")
    assert invokes == n
    print(f"interpreter: {rate:.0f} ops/s")
    assert rate > 5_000, f"interpreter too slow: {rate:.0f} ops/s"
