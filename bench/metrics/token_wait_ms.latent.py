"""The host's wait for sampled tokens per scheduler step in the traced
stretch: the ``token_wait_ms.serve`` reader, in the cells whose
end-to-end metric it moves is that of a latent-attention model."""
from bench.harness.loader import metric_reader

read = metric_reader("token_wait_ms.serve").read
