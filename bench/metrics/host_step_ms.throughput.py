"""``host_step_ms.serve`` read in a cell judged on tokens per second:
the host's work per scheduler step, from the program's spans."""
from bench.harness.loader import metric_reader

read = metric_reader("host_step_ms.serve").read
