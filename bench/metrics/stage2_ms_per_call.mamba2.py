"""Attribution core and model stage 2 in the Mamba-2 cell: device
milliseconds under ``repro.stage2`` per gradient-program call in the window
(see stage2_ms_per_call.backlog.py)."""
from bench.harness.spec import metric_reader


def read(ctx):
    return metric_reader("stage2_ms_per_call.backlog").read(ctx)
