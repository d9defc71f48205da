"""``idle_share.serve`` read in a cell judged on tokens per second: the
share of the traced window in which no operation ran on the chip."""
from bench.harness.loader import metric_reader

read = metric_reader("idle_share.serve").read
