"""Share of the window with no op on the device (`lib/readers.idle_pct`);
the alignment cell, which reports `frames_per_s.align`."""

from lib.readers import idle_pct as read  # noqa: F401
