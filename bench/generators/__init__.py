"""Table generators, one module per configuration ``generator``."""
