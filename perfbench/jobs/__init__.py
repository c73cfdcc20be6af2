"""Job kinds: one module each, found by the ``job`` a traffic file names."""
