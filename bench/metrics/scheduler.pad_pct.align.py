"""Pad frames over the frames the decode ran (`lib/readers.pad_pct`);
the alignment cell, which reports `frames_per_s.align`."""

from lib.readers import pad_pct as read  # noqa: F401
