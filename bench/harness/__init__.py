"""The benchmark's yardstick: generation, reference, trace reduction and the
cell plumbing that `bench/run.py` drives. Nothing here is edited to add a
cell: configurations, traffic mixes, limits and per-layer metrics are files
found by name."""
