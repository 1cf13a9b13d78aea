"""Operation and byte counts of each kernel, from its call's shapes."""
