"""table_cells_per_step: the mean dense-table width a dispatched dense
lane-step carries, S x 2^P cells, from the program's
jepsen_tpu_wgl_batch_table_cells_total{family="dense"} over
jepsen_tpu_wgl_batch_lane_steps_total{family="dense", kind="dispatched"}.

Every step a vmapped dense group advances steps each lane's whole
S x 2^P table, padding lanes included, so the share of the engine's work
each lane-step stands for grows with the table: 8 x 2^P for the
cas-register cells, 256 x 2^P for the multi-register one.

Read from `jepsen_tpu.telemetry.snapshot` when the metric is read, over
the whole run, as `batch_pad_share` is: every check relabels the same
base histories, which keeps every key's table. A program without the
counter, or a run with no dense batch dispatch, reads nothing."""

CELLS = "jepsen_tpu_wgl_batch_table_cells_total"
STEPS = "jepsen_tpu_wgl_batch_lane_steps_total"


def _labels(key):
    return dict(kv.split("=", 1) for kv in key.split(","))


def read(run):
    from jepsen_tpu import telemetry

    snap = telemetry.snapshot(prefix="jepsen_tpu_wgl_batch_")
    cells = sum(v for k, v in snap.get(CELLS, {}).items()
                if _labels(k).get("family") == "dense")
    steps = sum(v for k, v in snap.get(STEPS, {}).items()
                if _labels(k) == {"family": "dense", "kind": "dispatched"})
    if not cells or not steps:
        return None
    return cells / steps
