"""The least work a GET needs, counted from the request and its answer,
not from what the program does, so it stays the same whatever serves it.

A GET reads its request (the key), the H key words of its home
neighbourhood, and on a hit the V value words of the bucket that matched;
it writes its response (a found word and V value words).  All words are
4-byte int32.  Bytes over the chip's peak HBM bandwidth give the least
time; the roofline share is that over the device time measured.
"""
from __future__ import annotations

import json
import pathlib

WORD_BYTES = 4
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """The peak row of ``device_kind``; a device missing from the table
    is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def get_bytes(live: int, hits: int, neighborhood: int, val_words: int) -> int:
    """HBM bytes ``live`` GETs with ``hits`` hits need at the least."""
    request = 1
    probe = neighborhood
    response = 1 + val_words
    words = live * (request + probe + response) + hits * val_words
    return words * WORD_BYTES
