"""Plain references that decide ``correct``. Nothing here imports the
program (``rlgpuschedule_tpu``): numpy and plain ``jax`` only."""
