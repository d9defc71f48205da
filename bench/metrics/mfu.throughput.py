"""``mfu.serve`` read in a cell judged on tokens per second: model
operations of the window's steps over their wall time, as a share of the
chips' bf16 peak."""
from bench.harness.loader import metric_reader

read = metric_reader("mfu.serve").read
