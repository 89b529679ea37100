"""Bytes a SYMMETRY canonicaliser must move through HBM, from what it
canonicalised (PR 47).

A FLOOR, not a model of the program: every row that went through the
canonicaliser (`search.canon_rows`: every generated state once) is read once
and written once at its UNPACKED lane width — the width the canonicaliser
works at (`row_lanes` int32 lanes: alice, bob, N money lanes, N pc lanes) —
and nothing else.  Nothing is counted for the compare-exchange network
itself (elementwise work on what was read), for the padding of the
candidate blocks (rows that are not valid are canonicalised too, and
masked), nor for a layout of the [rows, lanes] block that pads its minor
dimension.  A canonicaliser that XLA fuses into its producer and consumer
moves FEWER bytes than this through HBM, and then no operation carries its
scope: the reader returns None, it does not guess (bench/SPANS.symmetry.md).
"""

from __future__ import annotations

WORD = 4  # unpacked rows are int32 lanes


def canon_bytes(rows: int, row_lanes: int) -> int:
    """rows x lanes x 4 B, read once and written once."""
    return 2 * rows * row_lanes * WORD
