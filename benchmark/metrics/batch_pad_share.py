"""batch_pad_share: the share of the batch engine's dispatched lane-steps
that did no key's work, 1 - useful / dispatched, from the program's
jepsen_tpu_wgl_batch_lane_steps_total over every family.

A vmapped dispatch group runs every lane, padding lanes included, for
every step its chunk loop advances (dispatched); a real key needs only
its own steps, up to those (useful). Keys of unequal length in one group,
and lane counts rounded up to a power of two, are what the share counts.

It does not follow the host's cost of the same padding: each lane is
built, attested and copied to the device with E rows, E being the
group's longest stream rounded up to a power of two, whatever steps the
loop then advances. The counter's `staged` kind (lanes x E) holds that.

Read from `jepsen_tpu.telemetry.snapshot` when the metric is read, as
`harness.chunk_seconds` reads the chunk timer, so it covers warm-up and
window alike. That holds for a share: every check relabels the same base
histories, and relabelling changes no key's length. A program without
the counter, or a run with no batch dispatch, reads nothing."""

from collections import Counter

FAMILY = "jepsen_tpu_wgl_batch_lane_steps_total"


def read(run):
    from jepsen_tpu import telemetry

    series = telemetry.snapshot(prefix=FAMILY).get(FAMILY, {})
    total = Counter()
    for labels, value in series.items():
        total[dict(kv.split("=", 1) for kv in labels.split(","))["kind"]] \
            += value
    if total["dispatched"] == 0:
        return None
    return 1 - total["useful"] / total["dispatched"]
