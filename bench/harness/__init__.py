"""Shared benchmark code: cell lookup, device checks, traffic, drivers,
trace reduction, operation counts and the output line."""
