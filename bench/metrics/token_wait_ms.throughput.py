"""``token_wait_ms.serve`` read in a cell judged on tokens per second:
the host's wait for sampled tokens per scheduler step."""
from bench.harness.loader import metric_reader

read = metric_reader("token_wait_ms.serve").read
