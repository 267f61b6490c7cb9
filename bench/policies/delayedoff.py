"""Delayed-off: wait Delta slots idle, then turn off; no peek."""
import numpy as np


def horizon(windows, delta):
    return np.zeros(len(windows), np.int64)


def static_wait(windows, delta):
    return np.full(len(windows), float(delta))
