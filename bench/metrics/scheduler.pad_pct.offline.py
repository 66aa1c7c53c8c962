"""Pad frames over the frames the decode ran (`lib/readers.pad_pct`);
the offline cells that report `frames_per_s`."""

from lib.readers import pad_pct as read  # noqa: F401
