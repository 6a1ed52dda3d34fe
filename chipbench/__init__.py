"""The chip benchmark: data-driven cells of served models on a TPU."""
