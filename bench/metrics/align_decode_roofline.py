"""The alignment decode program's share of its HBM roofline
(`lib/readers.decode_roofline`); the cell that reports `frames_per_s.align`."""

from lib.readers import decode_roofline as read  # noqa: F401
