"""Plain fp32 reference of the benchmark's configurations."""
