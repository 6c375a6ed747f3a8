"""The chip benchmark's yardstick: cell discovery, device checks, the token
generator, weights from the seed, checks, tracing and its reduction.

Nothing here imports the program under test; the drivers do.
"""
