"""One module per architecture: ``loss_and_aux(params, x, y, dropout_key,
dtype)`` in plain jax.numpy, over the parameter names the flax modules of
the program use (the weights are made by harness/seeded.py under those
names and handed to both sides)."""
