"""Share of the traced window in which no operation ran on the chip:
the ``idle_share.serve`` reader, for a latent-attention model."""
from bench.harness.loader import metric_reader

read = metric_reader("idle_share.serve").read
