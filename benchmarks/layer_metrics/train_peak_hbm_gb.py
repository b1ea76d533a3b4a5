"""`eval_peak_hbm_gb`'s reading in the train cells, where training
throughput is what it moves: a metric names one end-to-end metric, so
the reading has a name for each."""

from benchmarks.layer_metrics.eval_peak_hbm_gb import read  # noqa: F401
