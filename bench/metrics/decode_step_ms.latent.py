"""Device time per launch of the decode program, from the trace: the
``decode_step_ms`` reader, in the cells whose end-to-end metric it moves
is that of a latent-attention model."""
from bench.harness.loader import metric_reader

read = metric_reader("decode_step_ms").read
