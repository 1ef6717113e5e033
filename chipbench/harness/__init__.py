"""Shared harness code: cell lookup, device peaks, spans, trace reduction,
FLOP/byte arithmetic, traffic generation and the result line."""
