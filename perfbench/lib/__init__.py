"""The benchmark's yardstick: everything a later PR may read and not change."""
