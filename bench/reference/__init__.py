"""Plain references of the benchmark: closed forms in float64 and a plain
Monte Carlo estimator in ``jax.numpy``.  Nothing here imports the system
under test."""
