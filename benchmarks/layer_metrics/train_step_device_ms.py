"""`eval_step_device_ms`'s reading in the train cells, where training
throughput is what it moves: a metric names one end-to-end metric, so
the reading has a name for each."""

from benchmarks.layer_metrics.eval_step_device_ms import read  # noqa: F401
