"""FedGBF chip benchmark (see bench/run.py)."""
