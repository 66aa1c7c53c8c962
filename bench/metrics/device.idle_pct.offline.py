"""Share of the window with no op on the device (`lib/readers.idle_pct`);
the offline cells that report `frames_per_s`."""

from lib.readers import idle_pct as read  # noqa: F401
