"""Shared code of the on-chip benchmark: loading by name, traffic, the
client loop, weights from the seed, trace reduction and the output check."""
