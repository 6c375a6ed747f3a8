"""The chip benchmark's yardstick: cell discovery, device checks, the token
generator, weights from the seed, checks, tracing and its reduction, and
what every plain reference builds from (``reference.py``, ``flops.py``).

Nothing here imports the program under test; the drivers do.
"""
