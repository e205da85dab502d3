"""The benchmark's yardstick: everything here is the measurement, not the
system under test.  Nothing in this package imports ``paddle_tpu``."""
