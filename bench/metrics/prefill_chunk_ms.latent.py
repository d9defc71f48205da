"""Device time per launch of the chunked-prefill program, from the
trace: the ``prefill_chunk_ms`` reader, for a latent-attention model."""
from bench.harness.loader import metric_reader

read = metric_reader("prefill_chunk_ms").read
