"""Per-layer metrics, one file each: ``LAYER``, the programs or counters it
reads, and ``read(obs)`` over a :class:`bench.run.Observation`, which
returns None where there is nothing to read."""
