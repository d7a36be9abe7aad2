"""repro — KaMPIng-style named-parameter collectives for JAX SPMD."""
