"""Bytes the backward walk of a counterexample must move through HBM, from
what it visited (PR 44).

A FLOOR, not a model of the program: every log row the walk really expanded
is read once (`search.trace_rows_expanded` rows — a level stops at the first
chunk that holds a parent, so this is rows VISITED, never whole levels: the
floor cannot exceed the work, and `trace_walk_hbm_roofline` cannot pass
100 %), and the result block is written once.  Nothing is counted for the
successors the walk generates, packs and compares (they need never leave the
chip's fast memory), nor for the chunks' padding.
"""

from __future__ import annotations

WORD = 4  # states are int32 words


def walk_bytes(rows_expanded: int, trace_len: int, state_words: int) -> int:
    """log rows read once + the [trace_len, state_words] result written."""
    return (rows_expanded + trace_len) * state_words * WORD
