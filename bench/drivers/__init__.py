"""One module per entry path of the program (see bench/registry.py)."""
