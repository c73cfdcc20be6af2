"""Readers: one module each, found by the ``reader`` a metric file names.
``read(obs, trace, cell, args)`` returns the value, or None where there is
nothing to read (the harness then leaves the metric out of the line)."""
