"""The benchmark's yardstick: traffic, client, harness, reference, trace
reduction and the operation counts."""
