"""The benchmark's yardstick: cell lookup, traffic generation, the driver
around the serving engine, trace reduction, work counts and the check."""
