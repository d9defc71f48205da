"""Host work per scheduler step in the traced stretch, from the
program's own spans: the ``host_step_ms.serve`` reader, in the cells
whose end-to-end metric it moves is that of a latent-attention model."""
from bench.harness.loader import metric_reader

read = metric_reader("host_step_ms.serve").read
