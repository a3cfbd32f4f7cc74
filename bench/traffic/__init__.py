"""Traffic mixes (data files) and the benchmark's copy of the deployment generator."""
