"""Device mesh construction and sharded solvers (ICI-scale node/pod axes)."""
