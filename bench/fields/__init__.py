"""Field generators of the benchmark, found by the name a
configuration gives under ``field.generator``."""
