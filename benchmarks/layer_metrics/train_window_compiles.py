"""`eval_window_compiles`'s reading in the train cells, where training
throughput is what it moves: a metric names one end-to-end metric, so
the reading has a name for each."""

from benchmarks.layer_metrics.eval_window_compiles import read  # noqa: F401
