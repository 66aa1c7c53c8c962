"""The offline decode program's share of its HBM roofline
(`lib/readers.decode_roofline`); the cells that report `frames_per_s`."""

from lib.readers import decode_roofline as read  # noqa: F401
