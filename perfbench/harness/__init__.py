"""The chip benchmark's harness: data, traffic, trace reduction, work
counts and the runners of each kind of traffic."""
