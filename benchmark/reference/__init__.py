"""Plain references: jax.numpy, float32, no kernels, no cache, nothing of
the program. ``decoder.py`` is the forward pass, ``train.py`` the loss,
gradients and AdamW, ``check.py`` the comparisons that decide ``correct``."""
