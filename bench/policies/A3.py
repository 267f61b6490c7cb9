"""A3, randomized: A1's peek, with each newly idle level's wait drawn as
A2's (span * log1p(u (e - 1))) plus an atom at 0 of mass
alpha / (e - 1 + alpha); the draws from the key, split as the engine
splits them."""
from bench.reference import peek_horizon as horizon  # noqa: F401
from bench.reference import peek_static_wait as static_wait  # noqa: F401
from bench.reference import wait_tables


def waits(key, n_slots, n_levels, windows, delta):
    return wait_tables(key, n_slots, n_levels, windows, delta, atom=True)
