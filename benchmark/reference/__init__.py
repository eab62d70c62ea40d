"""The plain reference: float32 at ``highest`` matmul precision, plain
``jax.numpy``, one batch row at a time. It imports nothing of draco_tpu and
is given nothing draco_tpu has made: the data set and the weights come from
the benchmark's own seeded generators (harness/seeded.py), the batch rows
from its own copy of the index policy (feed.py)."""
