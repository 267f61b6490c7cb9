"""Bytes a provisioning call's spec needs, counted from its shapes, and the
table of device peaks they are set against.

The count is the work of the specification, not of any implementation:
the demand rows and the predicted rows the peek reads come in, the
per-level parameters come in, and the schedule ``x`` and the three
per-level cost rows (energy, turn-on, turn-off) go out.  It leaves out
whatever an implementation keeps between slots (an on-matrix, wait
tables), so the same call reads the same bytes whichever route runs it.
"""
from __future__ import annotations

import json
import pathlib

INT32 = FLOAT32 = 4
#: per-level parameters a call reads: P, beta_on, beta_off, Delta
LEVEL_PARAMS = 4
#: per-level cost rows a call writes: energy, turn-on, turn-off
COST_ROWS = 3

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def spec_io_bytes(*, traces: int, windows: int, n_slots: int, n_levels: int,
                  predicted: bool = True) -> int:
    """Bytes in and out of one call over ``traces`` demand rows and
    ``windows`` prediction windows (each (trace, window) cell writes its own
    schedule and cost rows)."""
    rows_in = traces * (2 if predicted else 1) * n_slots * INT32
    params_in = LEVEL_PARAMS * n_levels * FLOAT32
    cells = traces * windows
    out = cells * (n_slots * INT32 + COST_ROWS * n_levels * FLOAT32)
    return rows_in + params_in + out


def peaks(device_kind: str, path=PEAKS_FILE) -> dict:
    """``{"hbm_bytes_per_s", "bf16_flops_per_s", "hbm_bytes", "source"}`` of
    a device kind; a kind not in the table is an error."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks table "
                       f"({sorted(table)})")
    return table[device_kind]
