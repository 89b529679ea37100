"""Bytes a whole search must move through HBM, from its shapes alone.

A FLOOR, not a model of the program: every generated candidate row is
written once and read once with its dedup key beside it (the expand step
produces it, the dedup step consumes it), and every distinct state's key is
written into the seen set once.  Nothing is counted for the sort's passes,
for capacity padding, for re-reading the seen set per level or for the
frontier's copy: those are what the program adds on top, and what
`search_hbm_roofline` therefore shows as distance from 100 %.  Nobody should
read the resulting fraction of a percent as a target of 100 %; it is the
scale on which "level time follows capacity, not work" (PERF.md) shrinks.
"""

from __future__ import annotations

WORD = 4  # the engines hold states and keys as int32 words


def search_bytes(generated: int, distinct: int, state_words: int,
                 key_words: int) -> int:
    """generated rows x (packed state + key) x 4 B, written and read once,
    plus the distinct keys once."""
    row = (state_words + key_words) * WORD
    return 2 * generated * row + distinct * key_words * WORD


def roofline_share(bytes_moved: float, busy_s: float,
                   hbm_bytes_per_s: float) -> float:
    """Per cent of the HBM roofline: the least time the bytes could take
    over the time the device was busy with them."""
    return 100.0 * (bytes_moved / hbm_bytes_per_s) / busy_s


def peak_for(device_kind: str, peaks: dict) -> dict:
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       f"add it with its source, do not default it")
    return peaks[device_kind]
